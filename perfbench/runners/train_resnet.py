"""ResNet-50 trained on a synthetic batch, as Horovod's own synthetic
benchmark does: ``hvd.init()``, ``hvd.DistributedOptimizer`` around SGD
with momentum, one jitted and donated train step, the batch made on the
device from the seed.

The step's arithmetic is a copy of ``horovod_tpu.benchmark._Rig`` (listed
in PERF.md for a later PR to delete the original); only public API of the
program is called.
"""

import time

import numpy as np

from perfbench.harness import core, counts, trainloop

#: the first step's loss (bf16 compute, float32 parameters and head)
#: against the float32 reference's on the same parameters and batch. At
#: initialisation the loss is near ln(1000) = 6.9 and bf16 keeps 8 bits
#: through 53 convolutions; a wrong normalisation, stride or label moves
#: it by tenths. Measured on the v5e: see PERF.md, section 2.
LOSS_TOL = 0.05


def run(ctx: core.Context) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    cfg, tr = ctx.config, ctx.traffic
    t_warm = time.perf_counter()
    hvd.init()
    n = ctx.chips
    devices = ctx.devices[:n]
    size, classes = cfg["image_size"], cfg["num_classes"]
    batch = tr["batch_per_chip"] * n
    mesh = Mesh(np.array(devices), ("dp",))
    sharded = NamedSharding(mesh, P("dp"))
    replicated = NamedSharding(mesh, P())
    model = ResNet50(num_classes=classes,
                     dtype=jnp.dtype(cfg["activation_dtype"]))
    reference = ctx.load_reference()

    def make(key):
        k_img, k_lab, k_par = jax.random.split(key, 3)
        images = jax.random.normal(k_img, (batch, size, size, 3),
                                   jnp.bfloat16)
        labels = jax.random.randint(k_lab, (batch,), 0, classes)
        variables = model.init(k_par, jnp.zeros((1, size, size, 3),
                                                jnp.bfloat16), train=True)
        return images, labels, variables

    images, labels, variables = jax.jit(
        make, out_shardings=(sharded, sharded, replicated))(
            core.seed_key(ctx.seed))
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = hvd.DistributedOptimizer(
        optax.sgd(cfg["learning_rate"] * n, momentum=cfg["momentum"]))
    opt_state = jax.jit(opt.init, out_shardings=replicated)(params)

    def loss_fn(p, bs, x, y):
        logits, updates = model.apply(
            {"params": p, "batch_stats": bs}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, updates["batch_stats"]

    def _step(p, bs, s, x, y):
        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, bs, x, y)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), bs, s, loss

    step = jax.jit(_step, donate_argnums=(0, 1, 2)).lower(
        params, batch_stats, opt_state, images, labels).compile()

    trainloop.note_program_memory(ctx, step)
    ctx.mark("compiled")
    ref_loss = float(reference.loss(params, images, labels))
    state = (params, batch_stats, opt_state)
    *state, first = step(*state, images, labels)
    first = float(first)
    *state, second = step(*state, images, labels)
    float(second)
    ctx.facts["warmup_s"] = time.perf_counter() - t_warm
    ctx.mark("warm")
    ctx.facts["train_flops_per_item"] = \
        counts.resnet50_train_flops_per_image(size, classes)

    state, losses = trainloop.measure(
        ctx, step, tuple(state), (images, labels), batch)

    return trainloop.outcome(ctx, first, ref_loss, losses, LOSS_TOL)
