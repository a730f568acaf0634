"""Parameters, operations and bytes of the GLM-4.7-Flash configuration,
from its shapes alone (the configuration file's keys): the yardstick of
``programs.mla_mtp_verify_roofline`` and
``programs.mla_mtp_prefill_roofline``. What the published layer and its
MTP module need, whatever implements them: the causal work of the
equations, so that a share can only read low. A query at position ``p``
has ``p + 1`` rows before it in each plane, the MTP module's plane too.
A pad column, a padded row (a stored latent row is 640 wide, the
equations' 576), the walk's rounding to a key block and the table's
unused slots do not count. A verify step needs both of its columns
through the main model (the drafted one is what the step checks) and
the MTP module at the positions it commits.
"""

BF16 = 2
F32 = 4


def param_counts(cfg: dict) -> dict:
    """Parameter counts by part of a layer and of the model as held."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    attention = (d * rq + rq * h * (dn + dr) + d * (r + dr)
                 + r * h * (dn + dv) + h * dv * d)
    norms = rq + r + 2 * d
    dense_ffn = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    width = cfg["n_routed_experts"]
    router = d * width + width                             # float32
    held = cfg["held_experts"][1] - cfg["held_experts"][0]
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    moe = layers - dense
    dense_layer = attention + dense_ffn + norms
    moe_layer = attention + router + shared + held * expert + norms
    mtp = cfg["num_nextn_predict_layers"] * (2 * d * d + moe_layer + 3 * d)
    table = cfg["vocab_size"] * d
    return {"attention": attention, "dense_ffn": dense_ffn,
            "expert": expert, "shared": shared, "router": router,
            "held_experts": held, "dense_layers": dense, "moe_layers": moe,
            "dense_layer": dense_layer, "moe_layer": moe_layer,
            "mtp": mtp, "embedding": table, "head": table,
            "resident": (dense * dense_layer + moe * moe_layer + mtp
                         + 2 * table + d)}


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights this chip holds: bfloat16, the routers and
    their correction biases float32."""
    c = param_counts(cfg)
    routers = c["moe_layers"] + cfg["num_nextn_predict_layers"]
    return c["resident"] * BF16 + routers * c["router"] * (F32 - BF16)


def latent_row_bytes(cfg: dict) -> int:
    """A token's latent row in one plane: ``[c_kv | k_rope]``."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def planes(cfg: dict) -> int:
    """Latent planes: the main layers' and the MTP module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def attention_flops(cfg: dict, queries: int, prefix: int) -> float:
    """One attention sublayer of ``queries`` new tokens after ``prefix``
    rows: the cheaper of the absorbed form (scores against ``r + dr``
    values a row, the context of ``r``, each query folded and unfolded)
    and the expanded one (keys and values rebuilt for every row of the
    chunk's context)."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    pairs = float(sum(prefix + i + 1 for i in range(queries)))
    absorbed = 2.0 * h * (pairs * (2 * r + dr) + queries * r * (dn + dv))
    expanded = 2.0 * h * (pairs * (dn + dr + dv)
                          + (prefix + queries) * r * (dn + dv))
    return min(absorbed, expanded)


def _moe_token_flops(cfg: dict, held_picks: float) -> float:
    c = param_counts(cfg)
    return cfg["hidden_size"] * cfg["n_routed_experts"] + c["shared"] \
        + held_picks * c["expert"]


def main_token_flops(cfg: dict, held_picks: float) -> float:
    """Two operations a weight a token multiplies through in the main
    model: the attention projections, the dense FFN, the router, the
    shared expert and ``held_picks`` routed experts of each MoE layer."""
    c = param_counts(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * c["attention"]
                  + c["dense_layers"] * c["dense_ffn"]
                  + c["moe_layers"] * _moe_token_flops(cfg, held_picks))


def mtp_token_flops(cfg: dict, held_picks: float) -> float:
    """The same through the MTP module: ``eh_proj``, its attention
    projections and its MoE layer."""
    c = param_counts(cfg)
    d = cfg["hidden_size"]
    return 2.0 * cfg["num_nextn_predict_layers"] * (
        2 * d * d + c["attention"] + _moe_token_flops(cfg, held_picks))


def prefill_chunk_flops(cfg: dict, queries: int, prefix: int,
                        held_picks: float) -> float:
    """Operations one prefill chunk needs: its live tokens through the
    main model and the MTP module, attention in every plane, the head
    at the one position each samples (the main model's token, the MTP
    module's draft)."""
    c = param_counts(cfg)
    return (queries * (main_token_flops(cfg, held_picks)
                       + mtp_token_flops(cfg, held_picks))
            + planes(cfg) * attention_flops(cfg, queries, prefix)
            + 2 * 2.0 * c["head"])


def prefill_chunk_bytes(cfg: dict, queries: int, prefix: int) -> float:
    """Bytes one prefill chunk has to move: every weight the chip holds
    once (a chunk's tokens touch every expert) and, in every plane, the
    chunk's rows written and the context's rows read once."""
    return weight_bytes(cfg) + planes(cfg) * latent_row_bytes(cfg) * (
        queries + prefix + queries)


def verify_bytes(cfg: dict, contexts, touched_per_layer: float,
                 accepted: float) -> float:
    """Bytes one verify step has to move for live lanes whose first
    column has ``contexts`` rows before it: the weights outside the
    routed experts once, the routed experts a live token picked
    (``touched_per_layer``, the mean a MoE layer, the MTP module's
    counted among them), and in each main plane a lane's context and
    its two new rows read and the two written; in the MTP plane its
    context and the ``1 + accepted`` committed rows."""
    c = param_counts(cfg)
    moe = c["moe_layers"] + cfg["num_nextn_predict_layers"]
    experts = moe * c["held_experts"] * c["expert"] * BF16
    row = latent_row_bytes(cfg)
    committed = 1.0 + accepted
    cache = sum(cfg["num_hidden_layers"] * (n + 2 + 2) * row
                + cfg["num_nextn_predict_layers"] * (n + 2 * committed) * row
                for n in contexts)
    return (weight_bytes(cfg) - experts
            + moe * touched_per_layer * c["expert"] * BF16 + cache)


def verify_flops(cfg: dict, contexts, held_picks: float,
                 accepted: float) -> float:
    """Operations one verify step needs: a live lane's two columns
    through the main model with attention and the head at both, then
    ``1 + accepted`` committed positions through the MTP module with
    its attention, and its head at the last."""
    c = param_counts(cfg)
    committed = 1.0 + accepted
    total = 0.0
    for n in contexts:
        total += (2 * main_token_flops(cfg, held_picks)
                  + cfg["num_hidden_layers"] * attention_flops(cfg, 2, n)
                  + 2 * 2.0 * c["head"]
                  + committed * mtp_token_flops(cfg, held_picks)
                  + cfg["num_nextn_predict_layers"] * committed
                  * attention_flops(cfg, 1, n) + 2.0 * c["head"])
    return total
