"""Plain reference of the GPT-2 architecture: float32 ``jax.numpy``, no
cache, no batching tricks, no kernels, matmuls at ``highest`` precision.

It reads the parameter tree of ``horovod_tpu.models.Transformer`` (so the
same seeded weights serve both) and follows GPT-2 (Radford et al. 2019;
``openai-community/gpt2-xl``): learned positions, pre-LayerNorm blocks,
causal softmax attention, a 4x GELU MLP, a final LayerNorm, the head tied
to the embedding. The repo's block departs from GPT-2 in three places,
and the reference follows the repo, since the weights are random:

* no bias on the attention and MLP projections (GPT-2 has them);
* LayerNorm epsilon 1e-6 (flax's default; GPT-2 uses 1e-5);
* GELU in its tanh form (GPT-2's ``gelu_new``: the same).

One layer is one jitted call, so the 48 layers compile once and nothing
the size of the model is copied or stacked.
"""


import jax
import jax.numpy as jnp

LN_EPS = 1e-6
PRECISION = "highest"


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@jax.jit
def embed(emb, pos, tokens):
    with jax.default_matmul_precision(PRECISION):
        s = tokens.shape[1]
        return emb.astype(jnp.float32)[tokens] \
            + pos.astype(jnp.float32)[None, :s]


@jax.jit
def layer(p, x):
    with jax.default_matmul_precision(PRECISION):
        p = _f32(p)
        s = x.shape[1]
        h = _layer_norm(x, p["ln1"])
        a = p["attn"]
        q = jnp.einsum("bse,ehd->bshd", h, a["wq"])
        k = jnp.einsum("bse,ehd->bshd", h, a["wk"])
        v = jnp.einsum("bse,ehd->bshd", h, a["wv"])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        causal = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        x = x + jnp.einsum("bshd,hde->bse", out, a["wo"])
        h = _layer_norm(x, p["ln2"])
        m = p["mlp"]
        return x + _gelu_tanh(h @ m["wi"]) @ m["wo"]


@jax.jit
def head(ln_f, emb, x):
    with jax.default_matmul_precision(PRECISION):
        x = _layer_norm(x, _f32(ln_f))
        return jnp.einsum("bse,ve->bsv", x, emb.astype(jnp.float32))


@jax.jit
def head_loss(ln_f, emb, x, targets):
    """Sum of the token cross-entropies of these rows."""
    logits = head(ln_f, emb, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def _trunk(params, tokens):
    x = embed(params["embedding"], params["pos_embedding"], tokens)
    n = sum(1 for k in params if k.startswith("layer_"))
    for i in range(n):
        x = layer(params[f"layer_{i}"], x)
    return x


def forward(params, tokens):
    """``(B, S)`` tokens -> ``(B, S, vocab)`` float32 logits."""
    return head(params["ln_f"], params["embedding"], _trunk(params, tokens))


def loss(params, tokens, targets, rows_at_once: int = 4):
    """Mean token cross-entropy over the whole batch, a few rows at a
    time so that the float32 scores and logits fit beside a training
    state."""
    total = 0.0
    for i in range(0, tokens.shape[0], rows_at_once):
        x = _trunk(params, tokens[i:i + rows_at_once])
        total += float(head_loss(params["ln_f"], params["embedding"], x,
                                 targets[i:i + rows_at_once]))
    return total / (tokens.shape[0] * tokens.shape[1])
