"""The GPT-2-shaped ``Transformer`` trained through
``make_transformer_train_step`` over a mesh of every chip of the cell:
AdamW through ``hvd.DistributedOptimizer``, parameters and optimizer
state sharded by the repo's rules, the batch made on the device from the
seed. The step initialises its weights from its own fixed key, and the
benchmark leaves that alone.
"""

import time

from perfbench.harness import core, counts, models, trainloop

#: the first step's loss (bf16 activations) against the float32
#: reference's on the same parameters and batch. At initialisation the
#: loss is near ln(50257) = 10.8; bf16 through 48 layers moves it in the
#: third decimal, a wrong mask, shift or sharding rule by tenths.
#: Measured on the v5e: see PERF.md, section 2.
LOSS_TOL = 0.05


def run(ctx: core.Context) -> dict:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel import MeshConfig, make_training_mesh
    from horovod_tpu.parallel.train import make_transformer_train_step

    cfg, tr = ctx.config, ctx.traffic
    t_warm = time.perf_counter()
    hvd.init()
    n = ctx.chips
    seq_len = tr["sequence_tokens"]
    model_cfg = models.transformer_config(cfg, max_seq_len=seq_len,
                                          remat=cfg["remat"])
    mesh = make_training_mesh(MeshConfig(**cfg["mesh"]), ctx.devices[:n])
    bundle = make_transformer_train_step(
        model_cfg, mesh, optimizer=optax.adamw(cfg["learning_rate"]))
    batch = tr["batch_per_chip"] * n
    reference = ctx.load_reference()

    def make(key):
        toks = jax.random.randint(key, (batch, seq_len + 1), 0,
                                  cfg["vocab_size"], jnp.int32)
        return toks[:, :-1], toks[:, 1:]

    tokens, targets = jax.jit(
        make, out_shardings=(bundle.batch_sharding, bundle.batch_sharding))(
            core.seed_key(ctx.seed))
    step = bundle.step.lower(bundle.params, bundle.opt_state, tokens,
                             targets).compile()
    # the reference first: the step donates the parameters it is given
    trainloop.note_program_memory(ctx, step)
    ctx.mark("compiled")
    ref_loss = reference.loss(nn.meta.unbox(bundle.params), tokens, targets,
                              rows_at_once=tr.get("reference_rows", 4))
    state = (bundle.params, bundle.opt_state)
    *state, first = step(*state, tokens, targets)
    first = float(first)
    *state, second = step(*state, tokens, targets)
    float(second)
    ctx.facts["warmup_s"] = time.perf_counter() - t_warm
    ctx.mark("warm")
    ctx.facts["train_flops_per_item"] = counts.gpt2_train_flops_per_token(
        cfg, seq_len)

    state, losses = trainloop.measure(
        ctx, step, tuple(state), (tokens, targets), batch * seq_len)

    return trainloop.outcome(
        ctx, first, ref_loss, losses, LOSS_TOL,
        mesh={a: n for a, n in mesh.shape.items() if n > 1})
