"""Parameters, operations and bytes of the LongCat-Flash configuration,
from its shapes alone (the configuration file's keys): the yardstick of
``programs.mla_moe_decode_roofline`` and ``programs.mla_moe_prefill_roofline``.
What the algorithm needs, never what a program happens to execute: the
gather of a whole block table, pad tokens, masked-out positions and a
zero-padded cache row do not count.
"""

BF16 = 2


def param_counts(cfg: dict) -> dict:
    """Parameter counts by part. ``resident`` is what this chip holds
    (``n_routed_experts`` experts a layer, the vocabulary slice);
    ``per_token`` the matmul weights every token multiplies through in a
    layer stack (no experts, no embedding lookup, no head)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    layers, vocab = cfg["num_layers"], cfg["vocab_size"]
    attention = (d * rq + rq * h * (dn + dr) + d * (r + dr)
                 + r * h * (dn + dv) + h * dv * d)
    attention_norms = rq + r
    dense_ffn = 3 * d * cfg["ffn_hidden_size"]
    expert = 3 * d * cfg["expert_ffn_hidden_size"]
    router = d * cfg["router_width"] + cfg["router_width"]
    layer = 2 * attention + 2 * attention_norms + 2 * dense_ffn + 4 * d \
        + router
    return {"attention": attention, "dense_ffn": dense_ffn,
            "expert": expert, "router": router, "layer_without_experts": layer,
            "embedding": vocab * d, "head": d * vocab,
            "per_token": layers * (2 * attention + 2 * dense_ffn
                                   + d * cfg["router_width"]),
            "resident": layers * (layer + cfg["n_routed_experts"] * expert)
            + 2 * vocab * d + d}


def cache_bytes_per_token(cfg: dict) -> int:
    """What a token leaves in the cache: the latent and the rope key of
    each of a layer's two attentions (the zero padding of a pool row is
    the program's, not the algorithm's)."""
    return (2 * cfg["num_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16)


def decode_bytes(cfg: dict, live_context_tokens: float,
                 experts_touched_per_layer: float) -> float:
    """Bytes one decode step has to read: every weight outside the
    experts and the head once (the router in float32), the held experts
    that a live token picked, and the cached rows of every live token."""
    c = param_counts(cfg)
    router_extra = cfg["num_layers"] * c["router"] * 2      # float32
    weights = (cfg["num_layers"] * c["layer_without_experts"]
               + c["head"] + cfg["hidden_size"]) * BF16 + router_extra
    experts = cfg["num_layers"] * experts_touched_per_layer \
        * c["expert"] * BF16
    return weights + experts \
        + live_context_tokens * cache_bytes_per_token(cfg)


def attention_flops(cfg: dict, queries: int, prefix: int) -> float:
    """Causal latent attention of ``queries`` new tokens after ``prefix``
    cached ones, one attention, by the cheaper of the two formulations.
    Absorbed: the query folded into latent space (``r + dr`` a score,
    ``r`` a context element) plus folding and unfolding each query.
    Expanded: keys and values rebuilt for every visible position, then
    attention at head width."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    pairs = queries * prefix + queries * (queries + 1) / 2.0
    absorbed = 2.0 * h * (pairs * (2 * r + dr) + queries * r * (dn + dv))
    expanded = 2.0 * h * (pairs * (dn + dr + dv)
                          + (prefix + queries) * r * (dn + dv))
    return min(absorbed, expanded)


def prefill_chunk_flops(cfg: dict, queries: int, prefix: int,
                        held_picks_per_token: float) -> float:
    """Operations one prefill chunk needs: two a weight for every live
    token through the layers' dense parts, the held experts its picks
    fall on, attention over the context the chunk had, and the head at
    the one position that is sampled."""
    c = param_counts(cfg)
    dense = 2.0 * queries * c["per_token"]
    experts = 2.0 * queries * cfg["num_layers"] * held_picks_per_token \
        * c["expert"]
    attention = 2 * cfg["num_layers"] * attention_flops(cfg, queries,
                                                        prefix)
    return dense + experts + attention + 2.0 * c["head"]


def prefill_chunk_bytes(cfg: dict, queries: int, prefix: int,
                        experts_touched_per_layer: float) -> float:
    """Bytes one prefill chunk has to move: the weights as a decode step
    reads them, the cached rows of its prefix read and its own written."""
    return decode_bytes(cfg, prefix + queries, experts_touched_per_layer)
