"""Plain reference of the LongCat-Flash architecture: float32
``jax.numpy``, matmuls at ``highest`` precision, no cache, no kernels,
no batching tricks. Written from the published description
(``meituan-longcat/LongCat-Flash-Chat`` ``config.json`` and the model's
technical report as the catalog summarises them); it shares nothing with
``horovod_tpu/models/longcat_flash.py`` but the names of the parameter
tree, so the same seeded weights serve both.

One layer is a *double* layer (two latent attentions ``A``, two gated
MLPs ``F``, one expert layer ``M`` whose output joins at the end)::

    h = h + A_0(in_0(h));  u = post_0(h);  m = M(u);  h = h + F_0(u)
    h = h + A_1(in_1(h));  h = h + F_1(post_1(h)) + m

``settings`` is the configuration file as it is run: the norm's epsilon,
the rotary base, the two ``mla_scale_*`` switches, the router's top-k
and scale, the published number of FFN experts (the router has that many
outputs plus ``zero_expert_num``), and ``held_experts``: the ids of the
FFN experts whose weights the tree holds. Picks on FFN experts outside
that range add nothing, here as in the program: the chip's share of the
layer is what goes on. The vocabulary is whatever slice the tree's
embedding and head hold.

It computes in blocks so that it fits beside ten gigabytes of resident
bfloat16 weights: every weight matrix is upcast inside the one call that
uses it, an expert at a time, attention a group of heads at a time.

Departures from the source, each also a line of the configuration
file's ``assumed``: ``kv_b_proj`` is read as its two halves
(``kv_b_proj_nope``, ``kv_b_proj_v``); a held expert is evaluated on
every token and weighed by zero where it was not picked.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
#: heads whose float32 scores are held at a time
HEAD_GROUP = 8


def _f32(a):
    return a.astype(jnp.float32)


@jax.jit
def _matmul(x, w):
    with jax.default_matmul_precision(PRECISION):
        return x @ _f32(w)


@functools.partial(jax.jit, static_argnames=("eps",))
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


@jax.jit
def _swiglu(x, gate, up, down):
    with jax.default_matmul_precision(PRECISION):
        g = x @ _f32(gate)
        return (g * jax.nn.sigmoid(g) * (x @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("theta",))
def _rotate(x, theta):
    """Interleaved rotary: the pairs ``(x[2i], x[2i+1])`` of a token at
    position ``p`` are one complex number turned by
    ``p * theta ** (-2i / d)``. ``x``: ``(B, S, ..., d)``."""
    d = x.shape[-1]
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2])
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    z = z * jax.lax.complex(jnp.cos(ang), jnp.sin(ang))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


@jax.jit
def _heads(q_nope, q_rope, c, kr, w_uk, w_uv):
    """Causal attention of one group of heads. ``q_*``: (B, S, g, .);
    ``c``: (B, S, r) latents; ``kr``: (B, S, dr) the rotated shared key;
    ``w_uk``/``w_uv``: (r, g, .) the group's halves of ``kv_b_proj``."""
    with jax.default_matmul_precision(PRECISION):
        k_nope = jnp.einsum("btr,rhd->bthd", c, _f32(w_uk))
        v = jnp.einsum("btr,rhd->bthd", c, _f32(w_uv))
        scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                  + jnp.einsum("bshd,btd->bhst", q_rope, kr))
        scores = scores / jnp.sqrt(
            jnp.float32(q_nope.shape[-1] + q_rope.shape[-1]))
        s = scores.shape[-1]
        causal = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhst,bthd->bshd", probs, v)


def attention(p, x, st):
    """MLA: low-rank queries, one latent and one rotary key a token."""
    d = x.shape[-1]
    rq, r = p["q_a_proj"].shape[1], p["kv_a_layernorm"].shape[0]
    heads, dn = p["kv_b_proj_nope"].shape[1:]
    eps, theta = st["rms_norm_eps"], float(st["rope_theta"])
    q = _rms(_matmul(x, p["q_a_proj"]), p["q_a_layernorm"], eps)
    q = _matmul(q, p["q_b_proj"].reshape(rq, -1)).reshape(
        x.shape[:2] + (heads, -1))
    if st["mla_scale_q_lora"]:
        q = q * (d / rq) ** 0.5
    q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], theta)
    ckr = _matmul(x, p["kv_a_proj"])
    c = _rms(ckr[..., :r], p["kv_a_layernorm"], eps)
    if st["mla_scale_kv_lora"]:
        c = c * (d / r) ** 0.5
    kr = _rotate(ckr[..., r:], theta)
    out = []
    for h in range(0, heads, HEAD_GROUP):
        g = slice(h, h + HEAD_GROUP)
        out.append(_heads(q_nope[:, :, g], q_rope[:, :, g], c, kr,
                          p["kv_b_proj_nope"][:, g], p["kv_b_proj_v"][:, g]))
    out = jnp.concatenate(out, axis=2)
    return _matmul(out.reshape(x.shape[:2] + (-1,)),
                   p["o_proj"].reshape(-1, d))


@functools.partial(jax.jit, static_argnames=("k", "scale"))
def _route(u, router, bias, k, scale):
    """``s = softmax(W_r u)`` in float32 over every output; the choice
    on ``s + b``; the weights ``scale * s``, not renormalised. Returns
    the (T, outputs) matrix of weights, zero where not chosen."""
    with jax.default_matmul_precision(PRECISION):
        s = jax.nn.softmax(u @ _f32(router), axis=-1)
    _, idx = jax.lax.top_k(s + _f32(bias), k)
    chosen = jnp.zeros(s.shape, jnp.bool_).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    return jnp.where(chosen, s * scale, 0.0)


def experts(p, u, st):
    """``M(u)``: the held FFN experts' part and the identity experts'."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    n_ffn = int(st["n_routed_experts_published"])
    first, end = st["held_experts"]
    w = _route(u, p["router"], p["e_score_correction_bias"],
               int(st["moe_topk"]), float(st["routed_scaling_factor"]))
    if st.get("tally") is not None:
        st["tally"].append(jnp.sum(w != 0.0, axis=0))
    # zero-compute experts: the identity, weighed
    m = jnp.sum(w[:, n_ffn:], axis=-1, keepdims=True) * u
    for e in range(first, end):
        j = e - first
        m = m + w[:, e:e + 1] * _swiglu(
            u, p["experts_gate"][j], p["experts_up"][j],
            p["experts_down"][j])
    return m.reshape(shape)


def layer(p, h, st):
    eps = st["rms_norm_eps"]
    h = h + attention(p["attn_0"], _rms(h, p["input_layernorm_0"], eps), st)
    u = _rms(h, p["post_attention_layernorm_0"], eps)
    m = experts(p["moe"], u, st)
    f = p["mlp_0"]
    h = h + _swiglu(u, f["gate_proj"], f["up_proj"], f["down_proj"])
    h = h + attention(p["attn_1"], _rms(h, p["input_layernorm_1"], eps), st)
    f = p["mlp_1"]
    return h + _swiglu(_rms(h, p["post_attention_layernorm_1"], eps),
                       f["gate_proj"], f["up_proj"], f["down_proj"]) + m


def forward(params, tokens, settings, tally=None):
    """``(B, S)`` tokens -> ``(B, S, vocab)`` float32 logits. A list
    given as ``tally`` receives, a layer, how many of the tokens picked
    each of the router's outputs."""
    if tally is not None:
        settings = dict(settings, tally=tally)
    h = _f32(params["embed_tokens"][tokens])
    n = sum(1 for k in params if k.startswith("layer_"))
    for i in range(n):
        h = layer(params[f"layer_{i}"], h, settings)
    h = _rms(h, params["norm"], settings["rms_norm_eps"])
    return _matmul(h, params["lm_head"])
