"""Synthetic training benchmark, the analogue of the reference's
examples/tensorflow2_synthetic_benchmark.py and
pytorch_synthetic_benchmark.py (defaults documented in
docs/benchmarks.rst:66-85: ResNet-50, batch 32 per worker, 10 warmup
batches, 10 iterations x 10 batches, reports img/sec per worker and total).

TPU-native execution: single-controller jit with the batch sharded over the
'dp' mesh axis; parameters replicated; gradients reduced by XLA's sharding
propagation; DistributedOptimizer wraps the optax chain (mode 2, see
optimizer.py). bfloat16 compute, fp32 params. Buffer donation keeps params
in-place across steps (HBM-friendly).
"""

import dataclasses
import time
from typing import Optional

import numpy as np


@dataclasses.dataclass
class BenchResult:
    images_per_sec_per_chip: float
    images_per_sec_total: float
    num_chips: int
    batch_per_chip: int
    iter_mean_s: float
    iter_std_s: float
    platform: str = "unknown"
    device_kind: str = "unknown"
    flops_per_step: Optional[float] = None
    mfu: Optional[float] = None


# Peak dense bf16 FLOP/s per chip by device kind (public spec-sheet numbers;
# used only to turn measured throughput into an MFU estimate).
_TPU_PEAK_BF16_FLOPS = (
    ("v6e", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5litepod", 197e12), ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s of one chip; None for a device that is not a
    TPU (no MFU is reported there). A TPU kind missing from the table is an
    error, not a default: an MFU against a guessed peak is a wrong number."""
    k = (device_kind or "").lower()
    for name, peak in _TPU_PEAK_BF16_FLOPS:
        if name in k:
            return peak
    if "tpu" in k:
        raise ValueError(
            f"no peak FLOP/s on record for TPU device_kind "
            f"{device_kind!r}; add it to _TPU_PEAK_BF16_FLOPS with its "
            f"source")
    return None


class _Rig:
    """Compiled benchmark state for one (model, batch) configuration:
    the synthetic batch, the parameters and optimizer state on every
    chip, and the donated train step, compiled once ahead of time and
    held as ``train_step``."""

    def __init__(self, batch_per_chip: int, image_size: int,
                 model_name: str, optimizer_name: str):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import horovod_tpu as hvd
        from .models import InceptionV3, ResNet18, ResNet50, ResNet101, VGG16

        if not hvd.is_initialized():
            hvd.init()

        devices = jax.devices()
        self.n = n = len(devices)
        self.batch_per_chip = batch_per_chip
        self.global_batch = global_batch = batch_per_chip * n
        self.platform = devices[0].platform
        self.device_kind = getattr(devices[0], "device_kind", self.platform)

        mesh = Mesh(np.array(devices), ("dp",))
        batch_sharding = NamedSharding(mesh, P("dp"))
        replicated = NamedSharding(mesh, P())

        # the benchmark trio of the reference's scaling table
        # (docs/benchmarks.rst:13-14): ResNet, VGG (dropout off for a
        # deterministic throughput workload; BN-free, exercising the
        # no-batch-stats path)
        builders = {
            "resnet18": lambda: ResNet18(num_classes=1000),
            "resnet50": lambda: ResNet50(num_classes=1000),
            "resnet101": lambda: ResNet101(num_classes=1000),
            "vgg16": lambda: VGG16(num_classes=1000, dropout_rate=0.0),
            # tf_cnn_benchmarks' name for it; canonical input is 299px
            # but any size >= 75 runs
            "inception3": lambda: InceptionV3(num_classes=1000,
                                              dropout_rate=0.0),
        }
        model = builders[model_name]()

        rng = jax.random.PRNGKey(0)
        self.images = jax.device_put(
            jax.random.normal(rng, (global_batch, image_size, image_size, 3),
                              jnp.bfloat16), batch_sharding)
        self.labels = jax.device_put(
            jax.random.randint(rng, (global_batch,), 0, 1000), batch_sharding)

        variables = jax.jit(
            lambda: model.init(jax.random.PRNGKey(1),
                               jnp.zeros((1, image_size, image_size, 3),
                                         jnp.bfloat16), train=True),
            out_shardings=replicated)()
        self.params = variables["params"]
        # BN-free models (VGG) have no batch_stats collection
        self._has_bn = "batch_stats" in variables
        self.batch_stats = variables.get("batch_stats", {})

        # LR scaled by device count, the reference's hvd.size() recipe
        # (examples/tensorflow2_synthetic_benchmark.py lr * hvd.size())
        base = {"sgd": optax.sgd(0.01 * n, momentum=0.9),
                "adam": optax.adam(1e-3)}[optimizer_name]
        opt = hvd.DistributedOptimizer(base)
        self.opt_state = jax.jit(opt.init, out_shardings=replicated)(
            self.params)

        has_bn = self._has_bn

        def loss_fn(p, bs, x, y):
            if has_bn:
                logits, updates = model.apply(
                    {"params": p, "batch_stats": bs}, x, train=True,
                    mutable=["batch_stats"])
                bs = updates["batch_stats"]
            else:
                logits = model.apply({"params": p}, x, train=True)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, bs

        def _step(p, bs, s, x, y):
            (loss, bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, bs, x, y)
            updates, s = opt.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return p, bs, s, loss

        # donate params/batch_stats/opt_state so XLA updates them in place.
        # Compiled once, ahead of time: the executable that runs the steps
        # is the one whose cost analysis supplies the FLOP count.
        self.train_step = jax.jit(_step, donate_argnums=(0, 1, 2)).lower(
            self.params, self.batch_stats, self.opt_state, self.images,
            self.labels).compile()
        flops = self.train_step.cost_analysis().get("flops")
        if not flops:
            raise RuntimeError(
                "XLA cost analysis reports no FLOP count for the train "
                "step, so no MFU can be derived from this run")
        self.flops_per_step = float(flops)

    def _run_batches(self, k):
        p, bs, s = self.params, self.batch_stats, self.opt_state
        loss = None
        for _ in range(k):
            p, bs, s, loss = self.train_step(p, bs, s, self.images,
                                             self.labels)
        # Host readback (not just block_until_ready) to fence the timing:
        # the whole step chain must have executed for the loss value to
        # materialize; some PJRT transports complete block_until_ready on
        # scalars before device execution finishes.
        float(loss)
        self.params, self.batch_stats, self.opt_state = p, bs, s

    def run_stage(self, num_warmup_batches: int, num_batches_per_iter: int,
                  num_iters: int, verbose: bool = False) -> BenchResult:
        if num_warmup_batches:
            self._run_batches(num_warmup_batches)

        durations = []
        for i in range(num_iters):
            t0 = time.perf_counter()
            self._run_batches(num_batches_per_iter)
            dt = time.perf_counter() - t0
            durations.append(dt)
            if verbose:
                ips = self.global_batch * num_batches_per_iter / dt
                print(f"Iter #{i}: {ips:.1f} img/sec total")

        durations = np.array(durations)
        imgs = self.global_batch * num_batches_per_iter
        ips_total = float(np.mean(imgs / durations))

        peak = peak_flops_per_chip(self.device_kind)
        mfu = None
        if peak:
            steps_per_sec = ips_total / self.global_batch
            mfu = (self.flops_per_step * steps_per_sec) / (self.n * peak)

        return BenchResult(
            images_per_sec_per_chip=ips_total / self.n,
            images_per_sec_total=ips_total,
            num_chips=self.n,
            batch_per_chip=self.batch_per_chip,
            iter_mean_s=float(durations.mean()),
            iter_std_s=float(durations.std()),
            platform=self.platform,
            device_kind=self.device_kind,
            flops_per_step=self.flops_per_step,
            mfu=mfu,
        )


def synthetic_resnet50_benchmark(
        batch_per_chip: int = 32,
        num_warmup_batches: int = 10,
        num_batches_per_iter: int = 10,
        num_iters: int = 10,
        image_size: int = 224,
        model_name: str = "resnet50",
        optimizer_name: str = "sgd",
        verbose: bool = False) -> BenchResult:
    rig = _Rig(batch_per_chip, image_size, model_name, optimizer_name)
    return rig.run_stage(num_warmup_batches, num_batches_per_iter,
                         num_iters, verbose=verbose)
