"""Training-mesh construction.

The reference's GLOBAL/LOCAL/CROSS communicator triple
(/root/reference/horovod/common/common.h:111) generalizes on TPU to an
N-dimensional device mesh whose axis order encodes interconnect locality:
the **last** axes map to adjacent devices (ICI neighbors), the **first** axis
crosses slices (DCN). Collectives over trailing axes ride ICI; leading axes
ride DCN — so put tp/sp (latency-critical, per-layer) innermost and dp
(once-per-step gradient reduction) outermost, the standard scaling recipe.
"""

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np


class MeshShapeError(ValueError):
    """A mesh (re)shape request that cannot produce a valid device grid —
    survivor count not divisible by the protected inner axes, an unknown
    axis name in a spec, or a policy that refuses the change. Raised
    *before* any pjit trace, so the operator sees the policy and the
    counts instead of a shape error deep inside XLA."""


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes of each parallelism axis; -1 on dp means "absorb the rest"."""
    dp: int = -1      # data parallel (gradient allreduce, DCN-tolerant)
    fsdp: int = 1     # sharded params/optimizer (ZeRO-3 style)
    pp: int = 1       # pipeline stages
    ep: int = 1       # expert parallel
    sp: int = 1       # sequence/context parallel (ring attention)
    tp: int = 1       # tensor parallel (innermost, ICI-adjacent)


AXIS_ORDER = ("dp", "fsdp", "pp", "ep", "sp", "tp")

#: reshape policies for :func:`plan_reshape` (HVD_TPU_MESH_RESHAPE_POLICY)
RESHAPE_POLICIES = ("shrink", "degrade", "strict")


@dataclasses.dataclass(frozen=True)
class ReshapePlan:
    """Outcome of :func:`plan_reshape`: the new mesh config, the policy
    that produced it, the direction relative to the old shape ('down',
    'up', or 'none'), how many survivors the new mesh ``used``, and how
    many it ``dropped`` (non-zero only under the ``degrade`` policy)."""
    config: MeshConfig
    policy: str
    direction: str
    used: int
    dropped: int


def mesh_total(config: MeshConfig) -> int:
    """Devices a fully resolved config occupies (dp must not be -1)."""
    if config.dp <= 0:
        raise MeshShapeError(
            f"mesh config {config} has unresolved dp={config.dp}; resolve "
            "dp against a concrete device count first")
    return int(np.prod([getattr(config, a) for a in AXIS_ORDER]))


def mesh_config_from_spec(spec: str) -> MeshConfig:
    """Parse an ``axis=size`` comma list (``"dp=2,fsdp=2"``) into a
    MeshConfig. Unnamed axes default to 1 (an explicit spec is explicit —
    dp is not left at -1 unless the spec says ``dp=-1``)."""
    sizes = {a: 1 for a in AXIS_ORDER}
    if not spec or not spec.strip():
        raise MeshShapeError("empty mesh spec; expected 'axis=size' comma "
                             f"list over axes {AXIS_ORDER}")
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        axis, sep, value = part.partition("=")
        axis = axis.strip()
        if not sep or axis not in AXIS_ORDER:
            raise MeshShapeError(
                f"unknown mesh axis {axis!r} in spec {spec!r}; valid axes "
                f"(outermost first) are {AXIS_ORDER}")
        try:
            sizes[axis] = int(value)
        except ValueError:
            raise MeshShapeError(
                f"mesh axis {axis!r} has non-integer size {value!r} in "
                f"spec {spec!r}") from None
    return MeshConfig(**sizes)


def _inner_product(config: MeshConfig) -> int:
    """Product of the protected axes (everything but dp and fsdp): the
    reshape policies never break pp/ep/sp/tp groups — a tp-sharded matmul
    cannot lose a shard-holder and stay a matmul."""
    return int(np.prod([getattr(config, a) for a in AXIS_ORDER
                        if a not in ("dp", "fsdp")]))


def plan_reshape(config: MeshConfig, survivors: int,
                 policy: Optional[str] = None) -> ReshapePlan:
    """Compute the mesh shape ``survivors`` devices/hosts re-form into.

    Policies (``HVD_TPU_MESH_RESHAPE_POLICY``):

    * ``shrink`` (default): shrink dp first, then fsdp, never the inner
      (pp/ep/sp/tp) axes. Survivors must divide into whole inner groups
      or :class:`MeshShapeError` is raised.
    * ``degrade``: like shrink, but a survivor count that doesn't divide
      evenly drops a remainder (whole dp replica groups' worth of
      capacity idles) instead of aborting — ``plan.dropped`` says how
      many survivors sit out.
    * ``strict``: any change of shape raises :class:`MeshShapeError`
      (the operator wants a failed host to fail the job).

    ``config.dp == -1`` is resolved against ``survivors`` (first
    generation); the result's direction is ``'none'`` — adopting an
    initial shape is not a reshape.
    """
    if policy is None:
        from .. import config as _config
        policy = str(_config.live_config().get(
            _config.MESH_RESHAPE_POLICY)).strip().lower()
    if policy not in RESHAPE_POLICIES:
        raise MeshShapeError(
            f"unknown mesh reshape policy {policy!r}; valid policies are "
            f"{RESHAPE_POLICIES}")
    survivors = int(survivors)
    inner = _inner_product(config)
    if survivors < inner:
        raise MeshShapeError(
            f"policy {policy!r} cannot form a mesh from {survivors} "
            f"survivor(s): the protected inner axes (pp*ep*sp*tp) need "
            f"{inner} devices per replica group and are never broken")

    initial = config.dp <= 0
    old_total = None if initial else mesh_total(config)
    if not initial and survivors == old_total:
        return ReshapePlan(config=config, policy=policy, direction="none",
                           used=survivors, dropped=0)
    if not initial and policy == "strict":
        raise MeshShapeError(
            f"policy 'strict' refuses to reshape: mesh "
            f"{dataclasses.asdict(config)} needs {old_total} devices but "
            f"{survivors} survive")

    fsdp = max(int(config.fsdp), 1)
    if policy == "degrade":
        new_fsdp = fsdp
        while survivors // (new_fsdp * inner) < 1:
            new_fsdp -= 1   # terminates: survivors >= inner, so fsdp=1 fits
        new_dp = survivors // (new_fsdp * inner)
        used = new_dp * new_fsdp * inner
    else:
        if survivors % inner != 0:
            raise MeshShapeError(
                f"policy {policy!r} cannot reshape to {survivors} "
                f"survivor(s): not divisible by the protected inner-axes "
                f"product {inner} (pp*ep*sp*tp); use policy 'degrade' to "
                f"drop the remainder instead of aborting")
        q = survivors // inner
        if policy == "strict" and q % fsdp != 0:
            raise MeshShapeError(
                f"policy 'strict' cannot resolve dp: {survivors} "
                f"survivor(s) leave {q} inner groups, not divisible by "
                f"fsdp={fsdp}")
        new_fsdp = fsdp if q % fsdp == 0 else max(
            f for f in range(1, fsdp + 1) if q % f == 0)
        new_dp = q // new_fsdp
        used = survivors
    new_config = dataclasses.replace(config, dp=new_dp, fsdp=new_fsdp)
    if initial:
        direction = "none"
    else:
        direction = "down" if used < old_total else "up"
    return ReshapePlan(config=new_config, policy=policy, direction=direction,
                       used=used, dropped=survivors - used)


def replica_groups(world_size: int, dp: int) -> List[List[int]]:
    """Rank groups holding bit-identical parameter replicas.

    With dp outermost (AXIS_ORDER), rank = dp_index * (world/dp) +
    inner_index — so ranks sharing an inner index across dp slices hold
    the same tp/fsdp shard and may be fingerprint-compared; ranks in
    different groups hold *different* shards and must not be.
    """
    if dp <= 0 or world_size <= 0 or world_size % dp != 0:
        raise MeshShapeError(
            f"cannot form replica groups: world size {world_size} not "
            f"divisible into dp={dp} replicas")
    stride = world_size // dp
    return [[g + k * stride for k in range(dp)] for g in range(stride)]


def replica_group_of(rank: int, world_size: int, dp: int) -> int:
    """Index (into :func:`replica_groups`) of the group ``rank`` is in."""
    if dp <= 0 or world_size <= 0 or world_size % dp != 0:
        raise MeshShapeError(
            f"cannot form replica groups: world size {world_size} not "
            f"divisible into dp={dp} replicas")
    return int(rank) % (world_size // dp)


def make_training_mesh(config: MeshConfig = MeshConfig(),
                       devices=None):
    """Build a Mesh with axes ('dp','fsdp','pp','ep','sp','tp').

    Axes of size 1 are kept (harmless to XLA, simplifies downstream specs).
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = {a: getattr(config, a) for a in AXIS_ORDER}
    fixed = int(np.prod([s for a, s in sizes.items() if a != "dp" and s > 0]))
    if sizes["dp"] == -1:
        if n % fixed != 0:
            raise ValueError(
                f"{n} devices not divisible by non-dp axes product {fixed}")
        sizes["dp"] = n // fixed
    total = int(np.prod(list(sizes.values())))
    if total != n:
        raise ValueError(
            f"mesh sizes {sizes} use {total} devices but {n} are available")
    arr = np.array(devices).reshape([sizes[a] for a in AXIS_ORDER])
    return Mesh(arr, AXIS_ORDER)


# Logical-axis -> mesh-axis rules for the transformer in models/transformer.py
# (flax nn.with_logical_partitioning names). 'embed' stays replicated across
# tp (activations shard over it only in sequence-parallel regions); params
# additionally shard over fsdp on their largest axis.
TRANSFORMER_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("vocab", "tp"),
    ("heads", "tp"),
    ("mlp", "tp"),
    ("embed", "fsdp"),
    ("kv", None),
)


# Where the training path's activations live (models/transformer.py names
# them with nn.with_logical_constraint). The batch divides over dp AND fsdp:
# that is what makes 'fsdp' ZeRO-3 — a parameter sharded over it is gathered
# for use, its gradient reduce-scattered, and every chip computes only its
# own rows. The activations' width is never sharded: left unnamed, the
# partitioner would carry the parameters' 'embed' -> 'fsdp' into every
# contraction and all-reduce whole-batch partial sums instead.
ACTIVATION_RULES: Tuple[Tuple[str, object], ...] = (
    ("act_batch", ("dp", "fsdp")),
    ("act_seq", "sp"),
    ("act_heads", "tp"),
    ("act_mlp", "tp"),
    ("act_vocab", "tp"),
    ("act_embed", None),
    ("act_kv", None),
)


def require_axes(mesh, *axis_names: str):
    """Fail fast when an axis name is not on ``mesh``.

    The runtime counterpart of the ``mesh-axis`` lint
    (docs/static_analysis.md): the lint proves *literal* axis names
    resolve, this check covers names that arrive in variables. Without
    it a typo'd axis surfaces as an opaque trace-time NameError deep
    inside shard_map — or, worse, a mispaired collective.
    """
    declared = tuple(mesh.axis_names)
    missing = [a for a in axis_names if a and a not in declared]
    if missing:
        raise ValueError(
            f"axis name(s) {missing} not on this mesh (declared axes, "
            f"outermost first: {declared}); pipeline/MoE stages must "
            f"agree on the mesh's axis inventory and order")


def batch_spec():
    """PartitionSpec for a (batch, ...) input: batch shards over dp and
    fsdp. Each fsdp member computes its own rows end to end, forward and
    backward, on parameters gathered for use (ACTIVATION_RULES)."""
    from jax.sharding import PartitionSpec as P
    return P(("dp", "fsdp"))


def fsdp_sharded_leaves(params):
    """Leaves of ``params`` that are genuinely ZeRO-sharded over the 'fsdp'
    mesh axis: their addressable shard is strictly smaller than the global
    leaf AND their PartitionSpec names 'fsdp'. Used by tests and the driver
    dryrun to PROVE fsdp>1 shards parameters rather than trusting the spec.
    """
    import jax
    return [
        p for p in jax.tree_util.tree_leaves(params)
        if p.addressable_shards[0].data.size < p.size
        and "fsdp" in str(p.sharding.spec)
    ]


def param_shardings(mesh, abstract_variables, rules=TRANSFORMER_RULES):
    """NamedShardings for a flax variables pytree annotated with
    with_logical_partitioning."""
    import jax
    from flax import linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    logical = nn.get_partition_spec(abstract_variables)
    mesh_specs = nn.logical_to_mesh(logical, rules)
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), mesh_specs,
        is_leaf=lambda x: isinstance(x, P))


# -- what a compiled step communicates ----------------------------------------

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_HLO_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HLO_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.-]+ = (.*?) (" + "|".join(COLLECTIVE_KINDS)
    + r")(-start|-done)?\(")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\) -> (.*) \{$")
_HLO_CHANNEL = re.compile(r"channel_id=(\d+)")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective of a compiled program: its kind, the arrays it
    hands back as ``(dtype, shape)``, their bytes (on one device), and
    how many ``batch x sequence`` rows its widest array spans (0: none,
    or the census was not told the token shape)."""
    kind: str
    arrays: Tuple[Tuple[str, Tuple[int, ...]], ...]
    bytes: int
    rows: int = 0


@dataclasses.dataclass(frozen=True)
class CollectiveCensus:
    """:func:`collective_census`'s answer: the program's collectives in
    the order it prints them."""
    collectives: Tuple[Collective, ...]

    @property
    def by_kind(self) -> Dict[str, Dict[str, int]]:
        """Every kind of COLLECTIVE_KINDS -> ``{"count", "bytes"}``."""
        out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_KINDS}
        for c in self.collectives:
            out[c.kind]["count"] += 1
            out[c.kind]["bytes"] += c.bytes
        return out

    @property
    def batch_seq(self) -> Tuple[Collective, ...]:
        """The collectives whose ``rows`` is not 0."""
        return tuple(c for c in self.collectives if c.rows)

    def shapes(self, kind: str) -> set:
        """The distinct array shapes the collectives of ``kind`` return."""
        return {shape for c in self.collectives if c.kind == kind
                for _, shape in c.arrays}


def _hlo_arrays(type_text: str):
    return tuple(
        (dtype, tuple(int(d) for d in dims.split(",") if d))
        for dtype, dims in _HLO_ARRAY.findall(type_text)
        if dtype in _DTYPE_BYTES)


def _batch_seq_rows(shape, batches, seqs) -> int:
    """The most ``batch x sequence`` rows ``shape`` can be read to span:
    a batch extent with a sequence extent after it, or both merged into
    one axis."""
    rows = [b * s for i, b in enumerate(shape) if b in batches
            for s in shape[i + 1:] if s in seqs]
    rows += [d for d in shape for b in batches for s in seqs if d == b * s]
    return max(rows, default=0)


def collective_census(compiled_or_text, tokens_shape=None,
                      mesh=None) -> CollectiveCensus:
    """Count the collectives of a compiled program by kind.

    A step's layout is fixed when it is compiled, so its collectives say
    what the mesh axes do in it: an 'fsdp' axis that is ZeRO-3 shows
    all-gathers (and reduce-scatters or all-reduces) at parameter shapes
    and nothing at the activations' ``batch x sequence x width``.
    ``compiled_or_text``: a ``jax.stages.Compiled`` or its ``as_text()``.
    Reads the optimized HLO as the CPU and the TPU compilers print it: a
    collective an asynchronous pair carries is counted at its ``-done``;
    the operations one TPU collective is split into share a
    ``channel_id`` and count once; the TPU's ``all-reduce-scatter``
    fusion is a reduce-scatter of the fusion's result. Bytes are those
    of the arrays handed back on one device, unpadded.

    ``tokens_shape`` ``(batch, sequence)`` with ``mesh``: also give each
    collective its ``rows``, reading a shape's axes as the batch (whole,
    or divided over dp, fsdp or both) and the sequence (whole or over
    sp). A step whose chips each compute their own rows has no
    collective wider than a token (the targets, the loss's scalars)
    above ``batch x sequence / (dp * fsdp * sp)`` rows.
    """
    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    batches, seqs = (), ()
    if tokens_shape is not None and mesh is not None:
        batch, seq = tokens_shape
        dp, fsdp, sp = (mesh.shape.get(a, 1) for a in ("dp", "fsdp", "sp"))
        batches = {batch, batch // dp, batch // fsdp, batch // (dp * fsdp)}
        seqs = {seq, seq // sp}
    found: List[Collective] = []
    seen = set()
    scatter_result = None       # inside an all-reduce-scatter fusion
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            scatter_result = (head.group(2) if head.group(1).startswith(
                "all-reduce-scatter") else None)
            continue
        m = _HLO_COLLECTIVE.match(line)
        if not m or m.group(3) == "-start":
            continue
        result, kind = m.group(1), m.group(2)
        channel = _HLO_CHANNEL.search(line)
        if channel and m.group(3) is None:
            if (kind, channel.group(1)) in seen:
                continue
            seen.add((kind, channel.group(1)))
        if scatter_result is not None and kind == "all-reduce":
            result, kind = scatter_result, "reduce-scatter"
        arrays = _hlo_arrays(result)
        found.append(Collective(
            kind, arrays,
            sum(_DTYPE_BYTES[d] * int(np.prod(s, dtype=np.int64))
                for d, s in arrays),
            max((_batch_seq_rows(s, batches, seqs) for _, s in arrays),
                default=0)))
    return CollectiveCensus(tuple(found))
