"""Plain reference of the Command A+ architecture: float32 ``jax.numpy``,
matmuls at ``highest`` precision, every expert applied to every token
and weighed by the router (no sort, no grouped matmul), attention by
plain masked softmax (no cache, no kernel, no batching). Written from
the published configuration (``CohereLabs/command-a-plus-05-2026``
``config.json``) and the layer equations of ISSUE 33; it shares nothing
with ``horovod_tpu/`` but the names of the parameter tree, so the same
seeded weights serve both.

A layer, for the hidden vector ``h`` of a token at position ``p``::

    x = LN(h) = (h - mean h) / sqrt(var h + eps) * g      (no bias)
    q, k, v = W_q x, W_k x, W_v x;  query head j reads kv head j // (H/G)
    sliding layer: rotary on all of q and k, interleaved pairs (2i, 2i+1),
                   theta 50 000; keys p - W < t <= p
    full layer:    no position signal; keys t <= p
    a = W_o concat(heads);  scores / sqrt(head_dim), softmax in float32
    s = sigmoid(W_r x); I = the k largest s; w_i = s_i / sum_I s
    m = sum_{i in I, i held} w_i E_i(x) + (1/n) sum_j S_j(x)
    h = h + a + m

then ``LN`` and ``logit_scale * (LN(h) . E^T)`` against the embedding
table. ``E`` and ``S`` are ``W_down(silu(W_gate x) * W_up x)``. The
shared experts are read as ``n`` slices of the system's one wide gated
MLP and applied one by one.

**The held share is an argument** (``held``: ``(first, end)`` expert
ids; the parameter tree holds those experts' weights in id order): a
pick on an expert outside it adds nothing, as on the chip that holds a
share of a deployment. The uncut layer is ``held=(0, num_experts)``
with all the experts' weights.

It computes in blocks so that 33 k positions fit beside the served
bfloat16 weights: every weight matrix is upcast inside the one call that
uses it, a layer a block of rows at a time once the whole sequence's keys and
values are there, attention a block of queries and one key-value head
at a time (a sliding layer against
the ``W + block`` keys before the block's end, a full layer against the
whole sequence under the mask), the head a slice of the vocabulary at a
time. ``forward`` takes one sequence.

``faults`` computes one part wrongly, for the tolerance tool
(``perfbench/tools/command_a_plus_tolerance.py``): ``router_bf16``,
``softmax_bf16``, ``norm_bf16`` (that part in bfloat16, the nearest
precision below the float32 the configuration states),
``window_short`` (a window of ``W - 64``), ``rope_on_full`` (rotary on
the full layers too), ``rope_halves`` (rotary pairs ``(i, i + d/2)`` in
place of ``(2i, 2i + 1)``).
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
#: queries whose float32 scores (one key-value head's query heads against
#: the keys in reach) are held at a time
QUERY_BLOCK = 256
#: rows the expert layer takes at a time
ROW_BLOCK = 2048
#: vocabulary rows of the tied head upcast at a time
VOCAB_BLOCK = 8192
FAULTS = ("router_bf16", "softmax_bf16", "norm_bf16", "window_short",
          "rope_on_full", "rope_halves")


def _f32(a):
    return a.astype(jnp.float32)


@jax.jit
def _matmul(x, w):
    with jax.default_matmul_precision(PRECISION):
        return x @ _f32(w)


@functools.partial(jax.jit, static_argnames=("eps", "bf16"))
def layer_norm(x, w, eps, bf16=False):
    if bf16:
        x = x.astype(jnp.bfloat16)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return _f32(x * w.astype(x.dtype))


@functools.partial(jax.jit, static_argnames=("theta", "halves"))
def rotary(x, positions, theta, halves=False):
    """``x`` ``(T, heads, d)`` rotated by ``position * theta ** (-2i /
    d)`` in the pairs ``(2i, 2i + 1)`` (``halves``: ``(i, i + d/2)``)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = _f32(positions)[:, None, None] * freq          # (T, 1, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if halves:
        # out[i] = x[i] cos - x[i + d/2] sin, out[i + d/2] = x[i + d/2] cos
        # + x[i] sin: the other half comes by a roll, its sign by a mask
        sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)
        return x * jnp.tile(cos, 2) \
            + jnp.roll(x, d // 2, axis=-1) * jnp.tile(sin, 2) * sign
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("window", "soft_bf16"))
def _attend_block(q, k, v, q_start, k_start, window, soft_bf16):
    """Query block ``q`` ``(Q, G, rep, d)`` whose first row sits at
    position ``q_start`` against keys ``k``, ``v`` ``(K, G, d)`` whose
    first sits at ``k_start``; one key-value head at a time."""
    Q, K = q.shape[0], k.shape[0]
    at = q_start + jnp.arange(Q)[:, None]
    t = k_start + jnp.arange(K)[None, :]
    mask = (t <= at) & (t >= 0)
    if window:
        mask &= t > at - window

    def head(args):
        qh, kh, vh = args                       # (Q, rep, d), (K, d), (K, d)
        with jax.default_matmul_precision(PRECISION):
            s = jnp.einsum("qrd,kd->rqk", qh, kh) * q.shape[-1] ** -0.5
            s = jnp.where(mask, s, -jnp.inf)
            if soft_bf16:
                s = s.astype(jnp.bfloat16)
            p = _f32(jax.nn.softmax(s, axis=-1))
            return jnp.einsum("rqk,kd->qrd", p, vh)

    out = jax.lax.map(head, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                             v.transpose(1, 0, 2)))          # (G, Q, rep, d)
    return out.transpose(1, 0, 2, 3)


def keys_and_values(p, x, *, kv_heads, head_dim, theta, rope, at0=0,
                    faults=()):
    """``k``, ``v`` ``(T, G, d)`` of rows ``x`` whose first sits at
    position ``at0``, ``k`` rotated where the layer has rotary."""
    T = x.shape[0]
    k = _matmul(x, p["k_proj"]).reshape(T, kv_heads, head_dim)
    v = _matmul(x, p["v_proj"]).reshape(T, kv_heads, head_dim)
    if rope:
        k = rotary(k, at0 + jnp.arange(T), theta, "rope_halves" in faults)
    return k, v


def attention(p, x, k, v, at0, *, heads, kv_heads, head_dim, window, theta,
              rope, faults=()):
    """``a`` of one layer for the rows ``x`` ``(R, hidden)`` whose first
    sits at position ``at0``, against the whole sequence's ``k``, ``v``
    ``(T, G, d)``; a block of :data:`QUERY_BLOCK` queries at a time,
    projected, attended and projected back before the next."""
    T = k.shape[0]
    soft = "softmax_bf16" in faults
    out = []
    for a in range(0, x.shape[0], QUERY_BLOCK):
        xb = x[a:a + QUERY_BLOCK]
        rows, a = xb.shape[0], at0 + a
        q = _matmul(xb, p["q_proj"]).reshape(rows, heads, head_dim)
        if rope:
            q = rotary(q, a + jnp.arange(rows), theta,
                       "rope_halves" in faults)
        q = q.reshape(rows, kv_heads, heads // kv_heads, head_dim)
        if window:
            # the W + block keys before the block's end, wherever they
            # start (positions before 0 are masked): one shape a block
            q = jnp.pad(q, ((0, QUERY_BLOCK - rows),) + ((0, 0),) * 3)
            end = a + QUERY_BLOCK
            lo = end - window - QUERY_BLOCK
            pad = ((max(0, -lo), max(0, end - T)), (0, 0), (0, 0))
            got = _attend_block(q, jnp.pad(k[max(lo, 0):end], pad),
                                jnp.pad(v[max(lo, 0):end], pad), a, lo,
                                window, soft)[:rows]
        else:
            got = _attend_block(q, k, v, a, 0, None, soft)
        out.append(_matmul(got.reshape(rows, heads * head_dim),
                           p["o_proj"]))
    return jnp.concatenate(out)


@jax.jit
def _gated(x, gate, up, down):
    with jax.default_matmul_precision(PRECISION):
        return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def route(x, router, k, bf16=False):
    """``(idx (T, k), weights (T, k))``: sigmoid scores, the ``k``
    largest, divided by their sum."""
    if bf16:
        s = jax.nn.sigmoid(x.astype(jnp.bfloat16)
                           @ router.astype(jnp.bfloat16))
        s = _f32(s)
    else:
        with jax.default_matmul_precision(PRECISION):
            s = jax.nn.sigmoid(x @ _f32(router))
    picked, idx = jax.lax.top_k(s, k)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True)


def expert_layer(p, x, *, top_k, shared, held, faults=()):
    """``m`` of one layer for rows ``x`` ``(R, hidden)``: the routed
    experts in ``held`` weighed by the router, plus the mean of the
    ``shared`` shared experts."""
    first, end = held
    F = p["experts_gate"].shape[2]
    idx, w = route(x, p["router"], top_k, "router_bf16" in faults)
    m = jnp.zeros_like(x)
    for e in range(first, end):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # (R,)
        m = m + w_e[:, None] * _gated(
            x, p["experts_gate"][e - first], p["experts_up"][e - first],
            p["experts_down"][e - first])
    s = p["shared"]
    for j in range(shared):
        cols = slice(j * F, (j + 1) * F)
        m = m + _gated(x, s["gate_proj"][:, cols], s["up_proj"][:, cols],
                       s["down_proj"][cols]) / shared
    return m


def layer(p, h, settings, kind, held, faults=()):
    """One whole layer: ``h + a + m``, :data:`ROW_BLOCK` rows at a time
    once the keys and values of the whole sequence are there."""
    eps = float(settings["layer_norm_eps"])
    sliding = kind == "sliding_attention"
    window = int(settings["sliding_window"]) if sliding else None
    if window and "window_short" in faults:
        window -= min(64, window // 2)
    shape = dict(kv_heads=settings["num_key_value_heads"],
                 head_dim=settings["head_dim"],
                 theta=float(settings["rope_theta"]),
                 rope=sliding or "rope_on_full" in faults, faults=faults)
    norm = lambda rows: layer_norm(  # noqa: E731
        rows, p["input_layernorm"], eps, "norm_bf16" in faults)
    blocks = range(0, h.shape[0], ROW_BLOCK)
    kv = [keys_and_values(p["attn"], norm(h[a:a + ROW_BLOCK]), at0=a,
                          **shape) for a in blocks]
    k = jnp.concatenate([k for k, _ in kv])
    v = jnp.concatenate([v for _, v in kv])
    out = []
    for a in blocks:
        x = norm(h[a:a + ROW_BLOCK])
        att = attention(p["attn"], x, k, v, a, window=window,
                        heads=settings["num_attention_heads"], **shape)
        m = expert_layer(p["moe"], x, top_k=settings["num_experts_per_tok"],
                         shared=settings["num_shared_experts"], held=held,
                         faults=faults)
        out.append(h[a:a + ROW_BLOCK] + att + m)
    return jnp.concatenate(out)


def _head(h, emb, scale):
    """Float32 logits against the tied table, a slice of it at a time."""
    out = [_matmul(h, emb[i:i + VOCAB_BLOCK].T)
           for i in range(0, emb.shape[0], VOCAB_BLOCK)]
    return jnp.concatenate(out, axis=-1) * scale


def held_of(settings):
    return tuple(settings.get("held_experts")
                 or (0, settings["num_experts"]))


def forward(params, tokens, settings, at=None, held=None, faults=()):
    """Logits ``(1, S, vocab)`` float32 of one sequence ``tokens``
    ``(1, S)``; with ``at`` (an array of positions) at those positions
    only, ``(1, len(at), vocab)``. ``held``: the experts the parameter
    tree holds (None: the configuration's ``held_experts``)."""
    (row,) = tokens
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    held = held_of(settings) if held is None else tuple(held)
    kinds = settings["layer_types"][:settings["num_hidden_layers"]]
    h = _f32(params["embed_tokens"][row])
    for i, kind in enumerate(kinds):
        h = layer(params[f"layer_{i}"], h, settings, kind, held, faults)
    h = layer_norm(h, params["norm"], float(settings["layer_norm_eps"]),
                   "norm_bf16" in faults)
    return _head(h if at is None else h[at], params["embed_tokens"],
                 float(settings.get("logit_scale", 1)))[None]
