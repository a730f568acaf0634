"""DistributedOptimizer for JAX/optax.

Reference surface: ``hvd.DistributedOptimizer`` wraps a framework optimizer
so gradients are averaged across workers before the update
(/root/reference/horovod/torch/optimizer.py:100-186 — per-parameter hooks
firing async allreduces, step() synchronizes;
/root/reference/horovod/tensorflow/__init__.py:259-301 — compute_gradients
override). TPU-native redesign: the wrapper is an optax
``GradientTransformation`` whose ``update`` reduces gradients first, so it
composes with any optax chain and works in all three execution styles:

1. **Compiled data parallel inside shard_map** (the performance path):
   pass ``axis_name='dp'`` (and optionally ``inner_axis`` for hierarchical
   Adasum); reduction lowers to a single XLA psum/pmean over ICI — the
   NCCLAllreduce equivalent. ``packing='packed'`` fuses leaves into one
   variadic collective per memoized dtype bucket (the compiled-plane
   fusion buffer), and ``compression`` applies on the wire around each
   bucket's collective — bf16 half wire, fp16 upcast-psum, int8
   shared-scale quantization with an error-feedback residual carried as
   optax state (docs/injit.md).
2. **Single-controller pjit with sharded batch**: XLA's sharding propagation
   already produces globally-correct (mean-loss) gradients; the wrapper
   detects it is running under a trace without an ``axis_name`` and applies
   no extra reduction (wrapping is then harmless, matching "wrap once, runs
   anywhere").
3. **Eager host-plane** (one gradient pytree per process, the reference's
   process-rank model): gradients are bucketed (fusion.py, 64 MB default —
   HVD_TPU_FUSION_THRESHOLD), optionally compressed (compression.py), and
   reduced with fused eager allreduces.

``backward_passes_per_step`` (reference optimizer.py:100-186) is gradient
accumulation: raw gradients accumulate locally and the reduce+update runs
every k-th call (communication amortization), via ``optax.MultiSteps``.
"""

from typing import Any, NamedTuple, Optional

import numpy as np

from . import basics as _basics
from . import collectives as _c
from . import config as _config
from . import metrics as _metrics
from .compression import Compression

_M_STEPS = _metrics.counter(
    "hvd_tpu_optimizer_steps_total",
    "Eager DistributedOptimizer reduction steps (compiled-plane steps "
    "run inside jit and are counted by the training loop instead).")


class Int8ErrorFeedbackState(NamedTuple):
    """Optax state for ``Compression.int8``: the per-parameter
    error-feedback residual (fp32, same tree as the params) plus the
    wrapped base transform's state. The residual is what makes 8-bit
    wire training converge: each step's local quantization error is
    added back into the next step's gradient before quantizing
    (EF-SGD; compression.py int8_pack_reduce)."""
    residual: Any
    inner: Any


def _packed_threshold() -> int:
    """Bucket cap for the packed fusion buffers — the world's config when
    initialized (so programmatic overrides apply), the env/default
    resolution otherwise (pure shard_map training never calls init)."""
    if _basics.is_initialized():
        return _basics.world().config.get(_config.INJIT_PACKED_THRESHOLD)
    return _config.Config().get(_config.INJIT_PACKED_THRESHOLD)


class DistributedGradientTransform:
    """optax-compatible GradientTransformation that reduces gradients across
    the distributed world before delegating to ``base``."""

    def __init__(self, base, op=_c.Average, axis_name: Optional[str] = None,
                 inner_axis: Optional[str] = None,
                 compression=Compression.none,
                 prescale_factor: float = 1.0, postscale_factor: float = 1.0,
                 name_prefix: str = "DistributedOptimizer",
                 reduce_strategy: str = "hierarchical",
                 packing: str = "per_leaf"):
        if op not in (_c.Average, _c.Sum, _c.Adasum):
            raise ValueError(
                "DistributedOptimizer supports op=Average/Sum/Adasum "
                "(reference: torch/optimizer.py op argument).")
        if reduce_strategy not in ("hierarchical", "flat"):
            raise ValueError("reduce_strategy must be 'hierarchical' "
                             "(inner axis first, then outer — the "
                             "NCCLHierarchicalAllreduce shape) or 'flat' "
                             "(one collective over all axes)")
        if packing not in ("per_leaf", "packed"):
            raise ValueError("packing must be 'per_leaf' (one psum per "
                             "gradient leaf, XLA fuses) or 'packed' (one "
                             "fused collective per dtype bucket — the "
                             "fusion-buffer shape, fusion_buffer_manager.h"
                             ":30-55; docs/injit.md)")
        if getattr(compression, "stateful", False):
            # int8 needs the shared per-bucket scale (packed buffers) and
            # an error-feedback residual (optax state over the in-jit
            # reduction); neither exists on the eager or per-leaf paths.
            if axis_name is None or packing != "packed":
                raise ValueError(
                    "Compression.int8 requires the compiled packed path: "
                    "DistributedOptimizer(axis_name=..., packing='packed') "
                    "(docs/injit.md).")
            if op not in (_c.Average, _c.Sum):
                raise ValueError(
                    "Compression.int8 supports op=Average/Sum (Adasum "
                    "reduces in its own dtype-preserving recursion).")
        self._base = base
        self._op = op
        self._axis_name = axis_name
        self._inner_axis = inner_axis
        self._compression = compression
        self._prescale = prescale_factor
        self._postscale = postscale_factor
        self._prefix = name_prefix
        self._strategy = reduce_strategy
        self._packing = packing
        self._step = 0

    # optax protocol ---------------------------------------------------------
    @property
    def _stateful_compression(self) -> bool:
        return bool(getattr(self._compression, "stateful", False))

    def init(self, params):
        inner = self._base.init(params)
        if not self._stateful_compression:
            return inner
        import jax
        import jax.numpy as jnp
        residual = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
        return Int8ErrorFeedbackState(residual=residual, inner=inner)

    def update(self, grads, state, params=None, **extra):
        # the two named scopes cost nothing at run time: they name the
        # step's operations in a captured profile
        import jax
        if self._stateful_compression:
            if not isinstance(state, Int8ErrorFeedbackState):
                raise TypeError(
                    "Compression.int8 carries an error-feedback residual "
                    "as optax state; pass the state returned by this "
                    "transform's init() (got "
                    f"{type(state).__name__}).")
            with jax.named_scope("grad_reduce"):
                reduced, new_residual = self._packed_reduce(
                    grads, state.residual)
            with jax.named_scope("optimizer_update"):
                updates, inner = self._base.update(
                    reduced, state.inner, params, **extra)
            return updates, Int8ErrorFeedbackState(new_residual, inner)
        with jax.named_scope("grad_reduce"):
            reduced = self.reduce_gradients(grads)
        with jax.named_scope("optimizer_update"):
            return self._base.update(reduced, state, params, **extra)

    # reduction --------------------------------------------------------------
    def reduce_gradients(self, grads):
        import jax
        if self._axis_name is not None:
            return self._reduce_in_jit(grads)
        leaves = jax.tree_util.tree_leaves(grads)
        if leaves and any(isinstance(l, jax.core.Tracer) for l in leaves):
            # Mode 2: under jit/pjit without an explicit axis — XLA's
            # sharding propagation supplies globally-correct gradients.
            return grads
        return self._reduce_eager(grads)

    def _reduce_in_jit(self, grads):
        import jax

        if self._op == _c.Adasum:
            from .adasum import adasum_grads
            return adasum_grads(grads, outer_axis=self._axis_name,
                                inner_axis=self._inner_axis)

        def red(g):
            if self._prescale != 1.0:
                g = g * self._prescale
            if self._inner_axis is not None \
                    and self._strategy == "hierarchical":
                # hierarchical: reduce fast inner axis first (ICI), then
                # outer (DCN) — NCCLHierarchicalAllreduce shape,
                # nccl_operations.cc:178-372; XLA emits this as two
                # collectives that ride the right links.
                g = jax.lax.pmean(g, self._inner_axis)
                axes = self._axis_name
            elif self._inner_axis is not None:
                # flat: ONE collective over both axes; divide by the inner
                # size so the result matches the hierarchical semantics
                # (inner mean, outer op). Which wins depends on topology —
                # that's what compiled_autotune measures.
                axes = (self._inner_axis, self._axis_name)
            else:
                axes = self._axis_name
            if self._op == _c.Average:
                g = jax.lax.pmean(g, axes)
            else:
                g = jax.lax.psum(g, axes)
                if isinstance(axes, tuple):
                    g = g / jax.lax.psum(1.0, self._inner_axis)
            if self._postscale != 1.0:
                g = g * self._postscale
            return g

        if self._packing == "packed":
            reduced, _ = self._packed_reduce(grads, None)
            return reduced
        return jax.tree_util.tree_map(red, grads)

    def _packed_reduce(self, grads, residual):
        """Packed fusion buffers (docs/injit.md): leaves group per dtype
        into ``fusion.packed_plan`` buckets (capped by the
        HVD_TPU_INJIT_PACKED_THRESHOLD knob, 64 MB default — the
        reference's fusion-buffer cap), and each bucket runs as ONE XLA
        collective: a variadic
        all-reduce over the bucket's leaves for fp32/bf16/fp16 (the
        backend packs the buffer internally, fusion_buffer_manager.h:
        30-55 moved into the runtime; an explicit concatenate measured
        ~40x slower on the CPU sweep because XLA re-fuses it into the
        collective's operand), or one flat int8 buffer for the
        quantizing compressor (a shared per-bucket scale needs the flat
        view). ``residual`` (int8 error feedback) rides the same
        buckets. Returns ``(reduced_tree, new_residual_tree|None)``.
        """
        import jax
        from .fusion import packed_apply
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        res_leaves = None
        if residual is not None:
            res_leaves = jax.tree_util.tree_leaves(residual)
            if len(res_leaves) != len(leaves):
                raise ValueError(
                    "error-feedback residual tree does not match the "
                    "gradient tree (did the parameter structure change "
                    "without re-running init()?)")
        out, new_res = packed_apply(
            leaves, _packed_threshold(), self._reduce_bucket,
            residuals=res_leaves)
        reduced = jax.tree_util.tree_unflatten(treedef, out)
        if residual is None:
            return reduced, None
        return reduced, jax.tree_util.tree_unflatten(treedef, new_res)

    def _reduce_bucket(self, vals, rvals):
        """Reduce ONE bucket (same-dtype leaves) over the configured axes
        with the wire compression applied around its single collective.
        Matches the per-leaf ``red`` numerics exactly when no compressor
        is set (prescale -> [inner mean] -> reduce -> [inner division] ->
        postscale, elementwise in the same order), so fp32 packed vs
        per_leaf is bit-identical. Returns ``(out_leaves,
        new_residuals | None)``.
        """
        import jax
        import jax.numpy as jnp
        lax = jax.lax
        orig_dtype = vals[0].dtype
        gs = list(vals)
        if self._prescale != 1.0:
            gs = [g * self._prescale for g in gs]
        inner_in_axes = False
        if self._inner_axis is not None and self._strategy == "hierarchical":
            # inner mean rides the fast links uncompressed; the wire
            # compressor targets the outer (DCN-shaped) collective
            gs = list(lax.pmean(tuple(gs), self._inner_axis))
            axes = self._axis_name
        elif self._inner_axis is not None:
            axes = (self._inner_axis, self._axis_name)
            inner_in_axes = True
        else:
            axes = self._axis_name
        comp = self._compression
        floating = jnp.issubdtype(orig_dtype, jnp.floating)
        average = self._op == _c.Average
        new_r = rvals
        if getattr(comp, "stateful", False) and floating:
            from .compression import int8_pack_reduce
            from .fusion import flatten_bucket
            flat, unflatten = flatten_bucket(gs)
            rflat, _ = flatten_bucket(rvals) if rvals is not None \
                else (None, None)
            r, nr = int8_pack_reduce(flat, rflat, axes, average)
            gs = unflatten(r)
            new_r = unflatten(nr) if rvals is not None else None
        elif getattr(comp, "wire_dtype", None) is not None and floating:
            gw = tuple(g.astype(comp.wire_dtype) for g in gs)  # the wire
            if not comp.sum_safe_wire:
                # upcast-psum: fp16's 5-bit exponent overflows under
                # cross-replica Sum, so accumulate in fp32 (compression
                # keeps the rounding, concedes the wire bytes)
                gw = tuple(g.astype(jnp.float32) for g in gw)
            red = lax.pmean(gw, axes) if average else lax.psum(gw, axes)
            gs = [g.astype(jnp.float32) for g in red]
        else:
            gs = list(lax.pmean(tuple(gs), axes) if average
                      else lax.psum(tuple(gs), axes))
        if not average and inner_in_axes:
            # division, not reciprocal-multiply: bit-parity with red()
            inner_n = lax.psum(1.0, self._inner_axis)
            gs = [g / inner_n for g in gs]
        if self._postscale != 1.0:
            gs = [g * self._postscale for g in gs]
        return [g.astype(orig_dtype) for g in gs], new_r

    def _reduce_eager(self, grads):
        import jax
        from .fusion import bucketed_apply
        w = _basics.world()
        pm = w.parameter_manager
        autotuning = pm is not None and pm.active
        threshold = pm.fusion_threshold if autotuning \
            else w.config.get(_config.FUSION_THRESHOLD)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        self._step += 1
        _M_STEPS.inc()
        # stable names across steps: the ResponseCache fast path and the
        # reference's per-parameter naming (torch/optimizer.py:111-117) both
        # key on them; duplicate in-flight protection comes from the
        # TensorTable, and each bucket completes before the next begins
        names = [f"{self._prefix}.grad.{i}" for i in range(len(leaves))]

        def fused(bucket_vals, bucket_names):
            comp = [self._compression.compress(v) for v in bucket_vals]
            outs = _c.grouped_allreduce(
                [c for c, _ in comp], op=self._op,
                name=bucket_names[0] + ".bucket",
                prescale_factor=self._prescale,
                postscale_factor=self._postscale)
            return [self._compression.decompress(o, ctx)
                    for o, (_, ctx) in zip(outs, comp)]

        if not autotuning:
            reduced = bucketed_apply(leaves, threshold, fused, names)
            return jax.tree_util.tree_unflatten(treedef, reduced)

        # Autotune sampling: time the reduction (blocking — only while
        # tuning is active; reference ParameterManager likewise scores
        # wall time per negotiated batch, parameter_manager.cc Update).
        import time as _time
        nbytes = sum(
            int(np.prod(np.shape(l), dtype=np.int64))
            * np.dtype(getattr(l, "dtype", np.float32)).itemsize
            for l in leaves)
        t0 = _time.perf_counter()
        reduced = bucketed_apply(leaves, threshold, fused, names)
        jax.block_until_ready(reduced)
        pm.record(nbytes, _time.perf_counter() - t0)
        return jax.tree_util.tree_unflatten(treedef, reduced)


def DistributedOptimizer(optimizer, named_parameters=None,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         op=_c.Average, axis_name: Optional[str] = None,
                         inner_axis: Optional[str] = None,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         reduce_strategy: str = "hierarchical",
                         packing: str = "per_leaf"):
    """Wrap an optax optimizer so gradients are reduced across the world
    before each update (reference: hvd.DistributedOptimizer,
    torch/optimizer.py:372-420 factory).

    ``named_parameters`` is accepted for reference API parity; optax
    gradients are pytrees so names are derived from tree paths instead.
    ``reduce_strategy``/``packing`` select the compiled-plane reduction
    shape; :func:`horovod_tpu.compiled_autotune.tune_distributed_step`
    measures the variants and picks the fastest identically on every
    process.
    """
    dist = DistributedGradientTransform(
        optimizer, op=op, axis_name=axis_name, inner_axis=inner_axis,
        compression=compression, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        reduce_strategy=reduce_strategy, packing=packing)
    if backward_passes_per_step > 1:
        import optax
        return optax.MultiSteps(dist, every_k_schedule=backward_passes_per_step)
    return dist
