"""Plain reference of the GLM-4.7-Flash architecture with its multi-token
prediction module: float32 ``jax.numpy``, matmuls at ``highest``
precision, no cache, no kernels. Written from the published layer
(``zai-org/GLM-4.7-Flash`` ``config.json``, ``glm4_moe_lite``) and
DeepSeek-V3's multi-token prediction (arXiv:2412.19437, section 2.2);
it shares nothing with
``horovod_tpu/models/glm47_flash.py`` but the names of the parameter
tree, so the same seeded weights serve both. The router, the experts,
the norms and the rotary tables are those of the DeepSeek-V3.2
reference beside it (``deepseek_v32.py``), which is itself independent
of the program.

One layer, for the hidden state ``h`` of a sequence::

    x = rms(h); h = h + MLA(x); u = rms(h)
    h = h + (SwiGLU(u) if the layer is dense else routed(u) + shared(u))

MLA: ``c_q = rms(W_qa x)``, ``[q_nope | q_rope] = W_qb c_q`` a head,
``[c | k_rope] = W_kva x``, ``c = rms(c)``, ``k_nope = W_uk c``,
``v = W_uv c``, the interleaved rotary at ``rope_theta`` on ``q_rope``
and the one shared ``k_rope``, scores ``(q_nope . k_nope + q_rope .
k_rope) / sqrt(192 + 64)`` over positions ``s <= t``, a softmax, the
weighted ``v`` through ``o_proj``. The router: sigmoid scores, the
correction bias on the choice, one group, the top 4, weights
renormalised times ``routed_scaling_factor``.

The MTP module at position ``i`` (DeepSeek-V3, section 2.2)::

    u = W_eh [rms_e(Emb(t_{i+1})) ; rms_h(h_i)]      # h_i after the norm
    logits(t_{i+2}) = head(rms_s(MoE layer(u)))

its attention causal over the module's own positions ``0 .. S - 2``.

``settings`` is the configuration file as it is run. A routed expert is
evaluated on every token and weighed by zero where it was not picked.
Attention runs a block of :data:`QUERY_BLOCK` queries at a time.

``faults`` computes one part wrongly, for the tolerance tool
(:data:`FAULTS`): ``activations_float8`` rounds the residual stream and
what each sublayer reads to float8 (the nearest precision below the
bfloat16 the configuration states), ``mtp_float8`` does so inside the
MTP module alone, and ``mtp_no_hidden`` leaves ``h_i`` out of the
module's input.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import core

DS = core.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "deepseek_v32.py"), "perfbench_reference_deepseek_v32_for_glm")

PRECISION = DS.PRECISION
QUERY_BLOCK = 256
FAULTS = ("activations_float8", "mtp_float8", "mtp_no_hidden")
_NEG = -jnp.inf
_f32, _matmul, _rms, _float8 = DS._f32, DS._matmul, DS._rms, DS._float8


def rope_tables(settings):
    """The interleaved rotary of the rope slice at ``rope_theta``."""
    d = int(settings["qk_rope_head_dim"])
    theta = float(settings["rope_theta"])
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    return DS.rotary_tables(freq.astype(np.float32), d, interleaved=True)


def softmax_scale(settings):
    return (settings["qk_nope_head_dim"]
            + settings["qk_rope_head_dim"]) ** -0.5


@functools.partial(jax.jit, static_argnames=("heads", "eps", "scale", "qb"))
def _attention(p, x, c, kr, rope, heads, eps, scale, qb):
    """MLA over the ``S`` rows of ``x`` ``(S, D)``, ``qb`` queries at a
    time, keys and values rebuilt from ``c`` ``(S, r)`` and ``kr``
    ``(S, dr)`` (already rotated)."""
    S, D = x.shape
    n = -(-S // qb)
    f = {name: _f32(w) for name, w in p.items()}
    rq = f["q_a_proj"].shape[1]
    dn = f["kv_b_proj_nope"].shape[2]
    xp = jnp.pad(x, ((0, n * qb - S), (0, 0)))
    with jax.default_matmul_precision(PRECISION):
        k_nope = jnp.einsum("sr,rhd->shd", c, f["kv_b_proj_nope"])
        v = jnp.einsum("sr,rhd->shd", c, f["kv_b_proj_v"])

    def block(i):
        t = i * qb + jnp.arange(qb)
        seen = jnp.arange(S)[None, :] <= t[:, None]
        xb = jax.lax.dynamic_slice_in_dim(xp, i * qb, qb)
        with jax.default_matmul_precision(PRECISION):
            cq = xb @ f["q_a_proj"]
            cq = cq * jax.lax.rsqrt(jnp.mean(cq * cq, -1, keepdims=True)
                                    + eps) * f["q_a_layernorm"]
            q = (cq @ f["q_b_proj"].reshape(rq, -1)).reshape(qb, heads, -1)
            q_rope = DS._rotate(q[None, :, :, dn:], *rope, t)[0]
            scores = (jnp.einsum("qhd,shd->qhs", q[..., :dn], k_nope)
                      + jnp.einsum("qhd,sd->qhs", q_rope, kr)) * scale
            probs = jax.nn.softmax(
                jnp.where(seen[:, None, :], scores, _NEG), axis=-1)
            o = jnp.einsum("qhs,shd->qhd", probs, v)
            return o.reshape(qb, -1) @ f["o_proj"].reshape(-1, D)

    out = jax.lax.map(block, jnp.arange(n))
    return out.reshape(n * qb, D)[:S]


def attention(p, x, settings):
    """MLA over one sequence ``x`` ``(1, S, D)``."""
    r = p["kv_a_layernorm"].shape[0]
    heads = p["kv_b_proj_nope"].shape[1]
    eps = settings["rms_norm_eps"]
    rope = rope_tables(settings)
    ckr = _matmul(x, p["kv_a_proj"])
    c = _rms(ckr[..., :r], p["kv_a_layernorm"], eps)
    S = x.shape[1]
    kr = DS._rotate(ckr[..., r:], *rope, jnp.arange(S))
    return _attention(p, x[0], c[0], kr[0], rope, heads=heads, eps=eps,
                      scale=softmax_scale(settings), qb=QUERY_BLOCK)[None]


def layer(p, h, settings, dense, f8=False):
    eps = settings["rms_norm_eps"]
    r8 = _float8 if f8 else (lambda a: a)
    h = r8(h)
    h = r8(h + attention(p["attn"], r8(_rms(h, p["input_layernorm"], eps)),
                         settings))
    u = r8(_rms(h, p["post_attention_layernorm"], eps))
    if dense:
        f = p["mlp"]
        return r8(h + DS._by_rows(DS._swiglu, u[0], f["gate_proj"],
                                  f["up_proj"], f["down_proj"])[None])
    return r8(h + DS.experts(p["moe"], u, settings))


def main(params, tokens, settings, faults=()):
    """``(1, S)`` tokens -> the final hidden states ``(1, S, D)``, after
    the final norm."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    if tokens.shape[0] != 1:
        raise ValueError("the reference runs one sequence at a time")
    f8 = "activations_float8" in faults
    h = _f32(params["embed_tokens"][tokens])
    n = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n):
        h = layer(params[f"layers_{i}"], h, settings,
                  i < int(settings["first_k_dense_replace"]), f8)
    return _rms(h, params["norm"], settings["rms_norm_eps"])


def head(params, h, at=None):
    """Float32 logits of hidden states ``h`` ``(1, S, D)``, or at the
    positions ``at`` only."""
    if at is not None:
        h = h[:, jnp.asarray(at)]
    return _matmul(h, params["lm_head"])


def forward(params, tokens, settings, at=None, faults=()):
    """``(1, S)`` tokens -> float32 logits ``(1, S, vocab)`` of the main
    model, or at the positions ``at`` only."""
    return head(params, main(params, tokens, settings, faults), at)


def mtp(params, tokens, hidden, settings, at=None, faults=()):
    """The MTP module's logits of ``t_{i+2}`` ``(1, S - 1, vocab)`` for
    positions ``i = 0 .. S - 2`` of ``tokens`` ``(1, S)``, given the
    main model's final hidden states ``hidden`` ``(1, S, D)``; or at the
    positions ``at`` only (each at most ``S - 2``)."""
    p = params["mtp"]
    eps = settings["rms_norm_eps"]
    f8 = "activations_float8" in faults or "mtp_float8" in faults
    r8 = _float8 if f8 else (lambda a: a)
    e = r8(_rms(_f32(params["embed_tokens"][tokens[:, 1:]]), p["enorm"],
                eps))
    hh = r8(_rms(hidden[:, :-1], p["hnorm"], eps))
    if "mtp_no_hidden" in faults:
        hh = jnp.zeros_like(hh)
    u = _matmul(jnp.concatenate([e, hh], axis=-1), p["eh_proj"])
    g = layer(p["layer"], u, settings, dense=False, f8=f8)
    return head(params, _rms(g, p["shared_head_norm"], eps), at)
