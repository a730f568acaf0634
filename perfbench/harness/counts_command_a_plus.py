"""Parameters, operations and bytes of the Command A+ configuration, from
its shapes alone (the configuration file's keys): the yardstick of
``programs.swa_moe_decode_roofline``, ``programs.swa_moe_prefill_roofline``
and ``ops.paged_attention_roofline``. What the published layer needs,
whatever implements it: the gather of a block table, pad tokens,
masked-out positions, a pad column and a block read to fill out a
kernel's group do not count, and every byte is counted once.

A sliding layer's query at position ``p`` reads ``min(p + 1, W)`` keys,
a full layer's ``p + 1``. ``num_experts`` of the file counts the experts
held here; the router's width is the published count.
"""

BF16 = 2
F32 = 4


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def planes(cfg: dict) -> dict:
    """Attention sublayers by kind: ``{"full": n, "window": n}``."""
    kinds = layer_kinds(cfg)
    full = sum(k == "full_attention" for k in kinds)
    return {"full": full, "window": len(kinds) - full}


def param_counts(cfg: dict) -> dict:
    """Parameter counts by part of a layer and of the model as held."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, g, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    attention = d * h * hd + 2 * d * g * hd + h * hd * d
    shared = cfg["num_shared_experts"] * 3 * d * f
    router = d * cfg["published"]["num_experts"]
    expert = 3 * d * f
    held = cfg["num_experts"]
    layer = attention + shared + router + held * expert + d
    layers = len(layer_kinds(cfg))
    table = cfg["vocab_size"] * d
    return {"attention": attention, "shared": shared, "router": router,
            "expert": expert, "held_experts": held, "layer": layer,
            "layers": layers, "table": table,
            "outside_experts": layers * (layer - held * expert) + table + d,
            "resident": layers * layer + table + d}


def kv_bytes_per_token_plane(cfg: dict) -> int:
    """What a token leaves in one plane: its K and its V."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def block_bytes(cfg: dict, group: str) -> int:
    """Bytes of one block of plane group ``group``'s pools (K and V, in
    every plane of the group)."""
    return (planes(cfg)[group] * cfg["engine"]["block_size"]
            * kv_bytes_per_token_plane(cfg))


def uniform_held_picks(cfg: dict) -> float:
    """Picks a token a layer that fall on a held expert when the router
    picks uniformly."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def decode_bytes(cfg: dict, contexts, touched_per_layer: float) -> float:
    """Bytes one decode step has to move for live lanes whose contexts
    are ``contexts`` tokens: the weights outside the routed experts once
    (the tied table is the head's), the held experts a live token picked
    (``touched_per_layer``, the mean a layer), a live token's K and V on
    every full plane, and on every window plane those of the last
    ``min(context, W)``."""
    c, p = param_counts(cfg), planes(cfg)
    w = cfg["sliding_window"]
    row = kv_bytes_per_token_plane(cfg)
    return (c["outside_experts"] * BF16
            + c["layers"] * touched_per_layer * c["expert"] * BF16
            + p["full"] * row * float(sum(contexts))
            + p["window"] * row * float(sum(min(n, w) for n in contexts)))


def attention_pairs(queries: int, prefix: int, window=None) -> float:
    """Query-key pairs a causal mask (and a window) leaves for
    ``queries`` new tokens after ``prefix`` cached ones."""
    if not window:
        return queries * prefix + queries * (queries + 1) / 2.0
    return float(sum(min(prefix + i + 1, window) for i in range(queries)))


def attention_flops(cfg: dict, queries: int, prefix: int, window=None):
    """Scores and the weighted sum of one attention sublayer, every
    query head: two matmuls of ``head_dim`` a pair, two operations a
    multiply-add."""
    return (2.0 * 2.0 * attention_pairs(queries, prefix, window)
            * cfg["num_attention_heads"] * cfg["head_dim"])


def prefill_chunk_flops(cfg: dict, queries: int, prefix: int,
                        held_picks=None) -> float:
    """Operations one prefill chunk needs: two a weight for every live
    token through the attention projections, the shared experts, the
    router and the ``held_picks`` (a token a layer; None: uniform
    routing) held experts it picked; attention over the context the
    chunk had on a full plane and over the window on a window plane;
    the head at the one position that is sampled."""
    c, p = param_counts(cfg), planes(cfg)
    picks = uniform_held_picks(cfg) if held_picks is None else held_picks
    per_token = c["layers"] * (c["attention"] + c["shared"] + c["router"]
                               + picks * c["expert"])
    return (2.0 * queries * per_token
            + p["full"] * attention_flops(cfg, queries, prefix)
            + p["window"] * attention_flops(cfg, queries, prefix,
                                            cfg["sliding_window"])
            + 2.0 * c["table"])


def prefill_chunk_bytes(cfg: dict, queries: int, prefix: int) -> float:
    """Bytes one prefill chunk has to move: every weight the chip holds
    once (a chunk's tokens touch every held expert), the K and V of its
    context read and its own written on a full plane, and of the window
    before its last token on a window plane."""
    c, p = param_counts(cfg), planes(cfg)
    row = kv_bytes_per_token_plane(cfg)
    total = prefix + queries
    return (c["resident"] * BF16 + p["full"] * row * total
            + p["window"] * row * min(total, cfg["sliding_window"] + queries))
