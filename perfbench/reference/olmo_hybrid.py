"""Plain reference of the Olmo-Hybrid architecture: float32 ``jax.numpy``,
matmuls at ``highest`` precision, the linear layers' recurrence token by
token exactly as it is written, no chunked form, no cache, no batching.
Written from the published configuration
(``allenai/Olmo-Hybrid-7B`` ``config.json``) and the layer equations of
ISSUE 31; it shares nothing with ``horovod_tpu/`` but the names of the
parameter tree, so the same seeded weights serve both.

A layer, for both kinds: ``h = h + norm(mixer(h))``, ``h = h +
norm(mlp(h))``; a final norm before the untied head. A **full** layer:
``q, k, v = W x``, ``q`` and ``k`` RMS-normed over all their values,
30 heads of 128, causal softmax at ``128 ** -0.5``, no rotary. A
**linear** layer, with ``x`` its input::

    [q~ | k~ | v~] = W_qkv x;  u_t[c] = silu(sum_j w[j, c] u~_{t-3+j}[c])
    k_t = k / |k|;  q_t = q / |q| * dk ** -0.5          (per head)
    beta_t = 2 sigmoid(W_b x);  g_t = -exp(A_log) softplus(W_a x + dt_bias)
    S' = exp(g_t) S_{t-1};  r_t = beta_t (v_t - S' k_t);  S_t = S' + r_t k_t^T
    o_t = S_t q_t;  y_t = RMSNorm_dv(o_t) * silu(W_g x);  out = W_o y

with ``S`` a ``dv x dk`` matrix a head, zero before the first token: a
``lax.scan`` over time, one token a step.

``settings`` is the configuration file as it is run (``layer_types``,
the head counts and sizes, the norm's epsilon). It computes in blocks so
that it fits beside the served bfloat16 weights: every weight matrix is
upcast inside the one call that uses it, the head a slice of the
vocabulary at a time, full attention a block of queries at a time.
``forward`` takes one sequence.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
#: queries whose float32 scores over the whole sequence are held at a time
QUERY_BLOCK = 512
#: vocabulary columns of the head upcast at a time
VOCAB_BLOCK = 12544
L2_EPS = 1e-6


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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@functools.partial(jax.jit, static_argnames=("heads", "dk", "dv", "eps",
                                             "neg_eigval"))
def _linear_attention(p, x, heads, dk, dv, eps, neg_eigval):
    """``x``: ``(T, D)`` float32, one sequence from a zero state."""
    T = x.shape[0]
    with jax.default_matmul_precision(PRECISION):
        u = x @ _f32(p["qkv_proj"])                       # (T, 2 H dk + H dv)
        ab = x @ _f32(p["ab_proj"])                       # (T, 2 H)
        gate = x @ _f32(p["g_proj"])                      # (T, H dv)
    w = _f32(p["conv_weight"])                            # (taps, channels)
    taps = w.shape[0]
    ext = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    mixed = jax.nn.silu(sum(ext[j:j + T] * w[j] for j in range(taps)))
    q = _l2(mixed[:, :heads * dk].reshape(T, heads, dk)) * dk ** -0.5
    k = _l2(mixed[:, heads * dk:2 * heads * dk].reshape(T, heads, dk))
    v = mixed[:, 2 * heads * dk:].reshape(T, heads, dv)
    beta = jax.nn.sigmoid(ab[:, heads:]) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"]))
                    * jax.nn.softplus(ab[:, :heads] + _f32(p["dt_bias"])))

    def token(S, xs):                                     # S: (H, dv, dk)
        q_t, k_t, v_t, alpha_t, beta_t = xs
        with jax.default_matmul_precision(PRECISION):
            S = alpha_t[:, None, None] * S
            r = beta_t[:, None] * (v_t - jnp.einsum("hvk,hk->hv", S, k_t))
            S = S + r[:, :, None] * k_t[:, None, :]
            return S, jnp.einsum("hvk,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    y = _rms(o, p["o_norm"], eps) * jax.nn.silu(gate.reshape(T, heads, dv))
    with jax.default_matmul_precision(PRECISION):
        return y.reshape(T, heads * dv) @ _f32(p["o_proj"])


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _full_attention(p, x, heads, eps):
    """``x``: ``(T, D)``. Causal softmax attention with qk-norm, a block
    of queries at a time."""
    T = x.shape[0]
    with jax.default_matmul_precision(PRECISION):
        q = _rms(x @ _f32(p["q_proj"]), p["q_norm"], eps)
        k = _rms(x @ _f32(p["k_proj"]), p["k_norm"], eps)
        v = x @ _f32(p["v_proj"])
        hd = q.shape[1] // heads
        q, k, v = (a.reshape(T, heads, hd) for a in (q, k, v))
        out = []
        for start in range(0, T, QUERY_BLOCK):
            q_b = q[start:start + QUERY_BLOCK]
            scores = jnp.einsum("qhd,thd->hqt", q_b, k) * hd ** -0.5
            seen = (jnp.arange(T)[None, :]
                    <= (start + jnp.arange(q_b.shape[0]))[:, None])
            probs = jax.nn.softmax(
                jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            out.append(jnp.einsum("hqt,thd->qhd", probs, v))
        ctx = jnp.concatenate(out).reshape(T, heads * hd)
        return ctx @ _f32(p["o_proj"])


@jax.jit
def _mlp(p, x):
    with jax.default_matmul_precision(PRECISION):
        return (jax.nn.silu(x @ _f32(p["gate_proj"]))
                * (x @ _f32(p["up_proj"]))) @ _f32(p["down_proj"])


def _head(h, head):
    """Float32 logits, the vocabulary a slice at a time."""
    out = [_matmul(h, head[:, i:i + VOCAB_BLOCK])
           for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(out, axis=-1)


def forward(params, tokens, settings, at=None):
    """Logits ``(1, S, vocab)`` float32 of one sequence ``tokens``
    ``(1, S)``; with ``at`` (an array of positions) at those positions
    only, ``(1, len(at), vocab)``."""
    (row,) = tokens
    eps = float(settings["rms_norm_eps"])
    kinds = settings["layer_types"][:settings["num_hidden_layers"]]
    h = _f32(params["embed_tokens"][row])
    for i, kind in enumerate(kinds):
        p = params[f"layer_{i}"]
        if kind == "linear_attention":
            mixed = _linear_attention(
                p["linear_attn"], h, heads=settings["linear_num_value_heads"],
                dk=settings["linear_key_head_dim"],
                dv=settings["linear_value_head_dim"], eps=eps,
                neg_eigval=bool(settings["linear_allow_neg_eigval"]))
        else:
            mixed = _full_attention(
                p["attn"], h, heads=settings["num_attention_heads"], eps=eps)
        h = h + _rms(mixed, p["post_attention_layernorm"], eps)
        h = h + _rms(_mlp(p["mlp"], h), p["post_feedforward_layernorm"], eps)
    h = _rms(h, params["norm"], eps)
    return _head(h if at is None else h[at], params["lm_head"])[None]
