"""Parameters, operations and bytes of the Olmo-Hybrid configuration,
from its shapes alone (the configuration file's keys): the yardstick of
``programs.hybrid_decode_roofline`` and
``programs.hybrid_prefill_roofline``. What the algorithm needs, never
what a program happens to execute: the gather of a whole block table,
pad tokens, masked-out positions, a pad column's second pass over the
state and the padding of a stored array do not count, and every byte is
counted once.
"""

BF16 = 2
F32 = 4
#: tokens of a sub-chunk of the chunked delta rule
#: (``horovod_tpu.ops.gated_delta.SUBCHUNK``: the benchmark's own copy)
SUBCHUNK = 64


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def param_counts(cfg: dict) -> dict:
    """Parameter counts by part. ``per_token`` is the matmul weights
    every token multiplies through in the layer stack (no embedding
    lookup, no head); ``resident`` what the chip holds."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    taps = cfg["linear_conv_kernel_dim"]
    channels = h * (2 * dk + dv)
    linear_matmul = d * channels + d * 2 * h + d * h * dv + h * dv * d
    linear = linear_matmul + taps * channels + 2 * h + dv
    full_matmul = 4 * d * d
    full = full_matmul + 2 * d
    mlp = 3 * d * f
    kinds = layer_kinds(cfg)
    n_linear = sum(k == "linear_attention" for k in kinds)
    n_full = len(kinds) - n_linear
    vocab = cfg["vocab_size"]
    return {"linear_mixer": linear, "full_mixer": full, "mlp": mlp,
            "linear_layers": n_linear, "full_layers": n_full,
            "embedding": vocab * d, "head": d * vocab,
            "per_token": n_linear * linear_matmul + n_full * full_matmul
            + len(kinds) * mlp,
            "resident": n_linear * linear + n_full * full
            + len(kinds) * (mlp + 2 * d) + 2 * vocab * d + d}


def kv_bytes_per_token(cfg: dict) -> int:
    """What a token leaves in the paged pool: K and V of each full layer."""
    return param_counts(cfg)["full_layers"] * 2 * cfg["hidden_size"] * BF16


def state_bytes_per_sequence(cfg: dict) -> int:
    """What a sequence keeps whatever its length: each linear layer's
    float32 recurrent matrices and its convolution's last inputs."""
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    window = (cfg["linear_conv_kernel_dim"] - 1) * h * (2 * dk + dv) * BF16
    return param_counts(cfg)["linear_layers"] * (h * dk * dv * F32 + window)


def weight_bytes_read(cfg: dict) -> int:
    """Bytes of weights a program call reads once: everything resident
    but the embedding table, of which a call gathers a few rows."""
    c = param_counts(cfg)
    return (c["resident"] - c["embedding"]) * BF16


def decode_bytes(cfg: dict, live_context_tokens: float,
                 live_lanes: float) -> float:
    """Bytes one decode step has to move: the weights once in bfloat16,
    the K and V rows of every live token on the full layers' planes, and
    each live lane's state read once and written once."""
    return (weight_bytes_read(cfg)
            + live_context_tokens * kv_bytes_per_token(cfg)
            + 2.0 * live_lanes * state_bytes_per_sequence(cfg))


def delta_rule_flops(cfg: dict, tokens: int) -> float:
    """Operations of the chunked gated delta rule over ``tokens`` tokens
    of one linear layer, all heads: a sub-chunk of ``c`` tokens takes
    ``K K^T`` and ``Q K^T`` (``c c dk`` each), the triangular solve for
    ``dk + dv`` right-hand sides (``c c / 2`` each), ``W S`` and ``Q S``
    (``c dk dv`` each), the masked ``Q K^T`` times ``R`` (``c c dv``) and
    the state's ``K^T R`` (``c dk dv``); two operations a
    multiply-add."""
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    c = SUBCHUNK
    chunks = tokens / c
    per_chunk = 2.0 * (2 * c * c * dk + c * c * (dk + dv) / 2.0
                       + 3 * c * dk * dv + c * c * dv)
    return h * chunks * per_chunk


def attention_flops(cfg: dict, queries: int, prefix: int) -> float:
    """Causal softmax attention of ``queries`` new tokens after
    ``prefix`` cached ones, one full layer: scores and the weighted sum
    over the pairs a causal mask leaves."""
    pairs = queries * prefix + queries * (queries + 1) / 2.0
    return 2.0 * 2.0 * pairs * cfg["hidden_size"]


def prefill_chunk_flops(cfg: dict, queries: int, prefix: int) -> float:
    """Operations one prefill chunk needs: two a weight for every live
    token through the layers' matmuls, the chunked delta rule on the
    linear layers, attention over the context the chunk had on the full
    layers, and the head at the one position that is sampled."""
    c = param_counts(cfg)
    return (2.0 * queries * c["per_token"]
            + c["linear_layers"] * delta_rule_flops(cfg, queries)
            + c["full_layers"] * attention_flops(cfg, queries, prefix)
            + 2.0 * c["head"])


def prefill_chunk_bytes(cfg: dict, queries: int, prefix: int) -> float:
    """Bytes one prefill chunk has to move: the weights once, the K and
    V rows of its prefix read and its own written, and its sequence's
    state read once and written once."""
    return decode_bytes(cfg, prefix + queries, 1)
