"""The gated delta rule: a one-token form and a chunked form.

A linear-attention layer keeps, for a sequence and a head, one matrix
``S`` in place of a token's keys and values. A token ``t`` brings a key
``k_t`` and a query ``q_t`` (``dk`` values), a value ``v_t`` (``dv``
values), a decay ``alpha_t = exp(g_t)`` in (0, 1] and a step ``beta_t``
in [0, 2]::

    S'  = alpha_t S_{t-1}
    r_t = beta_t (v_t - S' k_t)
    S_t = S' + r_t k_t^T          o_t = S_t q_t

that is ``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t
k_t^T``. Both forms here hold the state **transposed**, ``(..., dk,
dv)`` (``S[k, v]``): every product of the chunked form then has ``dv``
as its free axis, and a row of the stored array is ``dv`` long.

:func:`gated_delta_step` is the recurrence as written, one token:
element-wise products and sums over the state, exact float32, what a
decode step takes. :func:`gated_delta_chunked` is the same recurrence
over ``T`` tokens in sub-chunks of ``chunk``. Inside a sub-chunk with
cumulative decay ``G_t = sum_{j<=t} g_j`` the pseudo-values ``r`` solve
a unit lower-triangular system,

    (I + A) R = diag(beta) V - diag(beta exp(G)) K S_0,
    A[t, i] = beta_t exp(G_t - G_i) (k_t . k_i)   for i < t,

so with ``W = (I + A)^-1 diag(beta exp(G)) K`` and ``U = (I + A)^-1
diag(beta) V`` (forward substitution: ``lax.linalg.triangular_solve``,
unrolled for a chunk of a few columns; stable where beta is near 2 and
the keys repeat, which a Neumann series of ``A`` is not), ``R = U - W
S_0`` and

    O   = diag(exp(G)) Q S_0 + (tril(exp(G_t - G_i)) * Q K^T) R
    S_c = exp(G_c) S_0 + (diag(exp(G_c - G)) K)^T R.

``W``, ``U`` and the masked ``Q K^T`` do not depend on the state and are
taken for all sub-chunks at once; a ``lax.scan`` over sub-chunks carries
the state through three matmuls (``[W; Q] S`` as one, so a sub-chunk
reads the state twice and writes it once). Every decay is the
exponential of a difference of cumulative sums that is never positive,
so an ``alpha`` near 0 underflows to 0 and nothing overflows. A token
with ``g = 0`` and ``beta = 0`` leaves the state bit-identical in both
forms (``1 * S + 0``): how the callers mask pad columns and dead lanes.

All of it is float32 at ``Precision.HIGHEST``: at the served widths the
chunked products are 33 GFLOP a 512-token chunk over 12 layers, a
hundredth of the chunk's weight matmuls.
"""

import jax
import jax.numpy as jnp

#: tokens of a sub-chunk of :func:`gated_delta_chunked`: the triangular
#: system is ``chunk x chunk``, the state is carried ``T / chunk`` times
SUBCHUNK = 64
#: up to this many rows the triangular system is solved by unrolled
#: forward substitution, element-wise: a decode step's few columns are
#: not worth a ``triangular_solve``
UNROLLED_ROWS = 8

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(state, q, k, v, g, beta):
    """One token. ``state``: ``(..., dk, dv)`` float32; ``q``, ``k``:
    ``(..., dk)``; ``v``: ``(..., dv)``; ``g``, ``beta``: ``(...)``.
    Returns ``(o (..., dv), state)``. ``o`` is taken from the old
    state's products, ``S_t q = alpha S q + r (k . q)``, so the state is
    read for its two products and once more for its update."""
    f32 = jnp.float32
    state, q, k, v = (a.astype(f32) for a in (state, q, k, v))
    alpha = jnp.exp(g.astype(f32))[..., None]
    sk = jnp.sum(state * k[..., :, None], axis=-2)
    sq = jnp.sum(state * q[..., :, None], axis=-2)
    r = beta.astype(f32)[..., None] * (v - alpha * sk)
    o = alpha * sq + r * jnp.sum(k * q, axis=-1, keepdims=True)
    state = alpha[..., None] * state + k[..., :, None] * r[..., None, :]
    return o, state


def gated_delta_recurrent(state, q, k, v, g, beta):
    """:func:`gated_delta_step` over the ``T`` columns of ``q``, ``k``
    ``(B, T, H, dk)``, ``v`` ``(B, T, H, dv)``, ``g``, ``beta``
    ``(B, T, H)``, unrolled: what a narrow chunk (a decode step's
    columns) takes. Returns ``(o (B, T, H, dv) float32, state)``."""
    outs = []
    for t in range(q.shape[1]):
        o, state = gated_delta_step(state, q[:, t], k[:, t], v[:, t],
                                    g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, axis=1), state


def _unit_lower_solve(a, rhs):
    """``(I + a) x = rhs`` for strictly lower ``a`` ``(..., c, c)`` and
    ``rhs`` ``(..., c, n)``: forward substitution."""
    c = a.shape[-1]
    if c > UNROLLED_ROWS:
        return jax.lax.linalg.triangular_solve(
            a + jnp.eye(c, dtype=a.dtype), rhs, left_side=True, lower=True,
            unit_diagonal=True)
    rows = []
    for t in range(c):
        x = rhs[..., t, :]
        for i in range(t):
            x = x - a[..., t, i, None] * rows[i]
        rows.append(x)
    return jnp.stack(rows, axis=-2)


def gated_delta_chunked(state, q, k, v, g, beta, chunk: int = SUBCHUNK):
    """The recurrence over ``T`` tokens by sub-chunks of ``chunk``
    (module docstring). ``state``: ``(B, H, dk, dv)`` float32; ``q``,
    ``k``: ``(B, T, H, dk)``; ``v``: ``(B, T, H, dv)``; ``g``, ``beta``:
    ``(B, T, H)``. ``T`` need not be a multiple of ``chunk``: the tail
    is filled with tokens of ``g = 0``, ``beta = 0``, which change
    nothing. Returns ``(o (B, T, H, dv) float32, state)``."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    c = min(int(chunk), T)
    n = -(-T // c)

    def by_chunk(a):        # (B, T, H, ...) -> (n, B, H, c, ...)
        a = a.astype(f32)
        a = jnp.pad(a, ((0, 0), (0, n * c - T)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = (by_chunk(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                              # (n, B, H, c)
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    # exp(G_t - G_i) for i <= t, 0 above the diagonal: masked before the
    # exponential, where the difference is positive and may overflow
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    a_mat = beta[..., None] * mm("nbhtk,nbhik->nbhti", k, k) * decay
    a_mat = jnp.where(rows[:, None] > rows[None, :], a_mat, 0.0)
    rhs = jnp.concatenate([(beta * jnp.exp(G))[..., None] * k,
                           beta[..., None] * v], axis=-1)
    wu = _unit_lower_solve(a_mat, rhs)
    u = wu[..., dk:]
    qk = mm("nbhtk,nbhik->nbhti", q, k) * decay
    # W and the decayed queries side by side: one product reads the state
    wq = jnp.concatenate([wu[..., :dk], jnp.exp(G)[..., None] * q], axis=-2)
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * k
    total = jnp.exp(G[..., -1])[..., None, None]            # (n, B, H, 1, 1)

    def carry(s, xs):
        wq_i, u_i, qk_i, k_i, total_i = xs
        ws_qs = mm("bhtk,bhkv->bhtv", wq_i, s)
        r = u_i - ws_qs[..., :c, :]
        o = ws_qs[..., c:, :] + mm("bhti,bhiv->bhtv", qk_i, r)
        s = total_i * s + mm("bhtk,bhtv->bhkv", k_i, r)
        return s, o

    state, o = jax.lax.scan(carry, state.astype(f32),
                            (wq, u, qk, k_out, total))
    # (n, B, H, c, dv) -> (B, T, H, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape((B, n * c, H, o.shape[-1]))[:, :T], state
