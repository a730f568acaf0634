"""Paged KV cache: fixed-size block pools + the block allocator.

vLLM's PagedAttention observation, applied to this stack: a dense KV
cache reserves ``max_len x batch`` per layer, but at any instant only
the *live* tokens matter. So the cache is a pool of fixed-size blocks
(``HVD_TPU_GEN_BLOCK_SIZE`` tokens each, ``HVD_TPU_GEN_NUM_BLOCKS`` of
them) and every sequence owns an ordered *block table* mapping its
logical block index to a pool block. Blocks are allocated on growth
(one at a time as decode crosses a block boundary, a run at once for a
prefill chunk) and freed the moment a sequence finishes or is
preempted — live KV memory tracks live tokens.

**Block 0 is the null block.** It is never handed out: the model routes
every padded-token and dead-lane write there
(:class:`horovod_tpu.models.transformer.PagedCache`), which is what
lets the compiled prefill/decode programs keep fully static shapes
while batch composition changes every step.

The allocator is strict by design: allocation is all-or-nothing
(:class:`BlocksExhaustedError` is the scheduler's preemption trigger,
never a partial grant) and :meth:`BlockAllocator.free` rejects
double-frees and foreign ids — a leak or a tangle fails the test that
caused it, instead of surfacing as silent cache corruption under load.
``hvd_tpu_gen_kv_blocks_in_use`` tracks the live block count;
:attr:`BlockAllocator.peak_in_use` is the high-water mark, to set
against what a dense reservation would hold.

**Automatic prefix caching** (``HVD_TPU_GEN_PREFIX_CACHE``, default
on) adds SGLang/vLLM-style block reuse on top. Every *full* block can
be registered under a content chain hash ``h(parent_hash,
block_tokens)`` — the hash commits to the whole token prefix, so two
blocks share a hash iff the cache contents feeding them were computed
from identical prefixes. Blocks become refcounted: a prompt that
matches a chain of indexed blocks attaches them with refcounts bumped
(:meth:`BlockAllocator.match`) and prefill starts at the first
uncached token. When the last reference drops, an indexed block parks
in a **cached-free LRU pool** with contents intact instead of being
recycled; allocation takes truly-free blocks first and only then
evicts cached blocks, least-recently-used first. Within one release
the blocks of a sequence are parked tail-first, so eviction consumes
a cached chain from its tail and the head prefix stays matchable.
Sharing is full-block-only — the partial tail block is always private
to one sequence — so no write ever lands in a shared block and
cached-prefix decode is bit-identical to cold decode.

**Per-sequence state** (a model whose
:class:`~horovod_tpu.models.transformer.CacheSpec` declares ``state``: a
linear attention's recurrent matrices, a convolution's window) lives
beside the blocks in the same manager. Every running sequence holds one
**state slot** (:meth:`BlockAllocator.take_state_slot`; the decode
program's lane ``i`` is slot ``i``). A cached block is worth nothing to
such a model without the state its sequence had after the block's last
token, so the allocator also keeps **snapshot slots**: a snapshot is
claimed for an indexed block (:meth:`BlockAllocator.claim_snapshot`; the
scheduler copies the state into it at a prefill-chunk boundary), is
evicted with that block or, when every slot is taken, least recently
used first, and :meth:`BlockAllocator.match` attaches no prefix deeper
than the deepest block that owns one. Snapshot 0 is the **null
snapshot**: zeros, never written, what a sequence with no hit starts
from. A model that declares no state has neither kind of slot and the
allocator behaves as it always did.

**Plane groups** (a model whose
:class:`~horovod_tpu.models.transformer.CacheSpec` declares ``groups``:
full-attention planes that keep every token beside sliding-window planes
that need a token for ``window`` positions). Each group has pools and an
allocator of its own over them; the first group's (it keeps every
token) is the allocator the engine holds, and the window group's hangs
on it as :attr:`BlockAllocator.window`. A sequence has one block list a
group. The scheduler gives a window block back
(:meth:`BlockAllocator.free`) as soon as its last position lies
``window`` or more behind the position about to be written; a released
block that is indexed parks in the window allocator's cached list and
stays matchable until evicted. Both allocators index a block under the
same chain hash, and :meth:`BlockAllocator.match` cuts a hit to the
deepest block boundary at which the window group still holds the
``window`` positions before it (:meth:`covered_depth`): a shallower
prefix of the full chain is worth more than a deeper one whose window
rows are gone. A model that declares no groups has no window allocator
and nothing here runs.
"""

import collections
import dataclasses
import functools
import hashlib
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ... import _locks
from ... import config as _config
from ... import metrics as _metrics
from ...models.transformer import PagedCache
from ...ops import paged_attention

_M_BLOCKS = _metrics.gauge(
    "hvd_tpu_gen_kv_blocks_in_use",
    "KV-cache blocks currently allocated to live generation sequences "
    "(the null block excluded). Live KV memory is this times the "
    "per-block byte size; pinning near HVD_TPU_GEN_NUM_BLOCKS means "
    "admission is block-bound and preemptions are imminent.")
_M_GROUP_BLOCKS = _metrics.gauge(
    "hvd_tpu_gen_kv_group_blocks_in_use",
    "KV-cache blocks held by live sequences, by plane group of the "
    "model's cache (group='full': planes that keep every token, the one "
    "group of most models, equal to hvd_tpu_gen_kv_blocks_in_use; "
    "group='window': sliding-window planes, whose blocks a running "
    "sequence gives back once the window has passed them). A window "
    "group near the full group's count means nothing is being released.",
    labels=("group",))
_M_WINDOW_RELEASED = _metrics.counter(
    "hvd_tpu_gen_window_blocks_released_total",
    "Window-group blocks running sequences gave back because every "
    "position in them lay a whole window behind the position being "
    "written (retirement and preemption are not counted). Indexed "
    "blocks among them park in the cached list and stay matchable.")
_M_BLOCK_STATE = _metrics.gauge(
    "hvd_tpu_gen_kv_blocks",
    "KV-cache block pool split by state (the null block excluded): "
    "free=never-written or recycled, cached=contents intact in the "
    "prefix-cache LRU pool awaiting reuse or eviction, private=held by "
    "exactly one live sequence, shared=prefix blocks referenced by two "
    "or more live sequences. The four states always sum to the pool "
    "capacity.",
    labels=("state",))
_M_EVICTIONS = _metrics.counter(
    "hvd_tpu_gen_prefix_cache_evictions_total",
    "Cached-free KV blocks whose contents were discarded to satisfy an "
    "allocation (free list empty, LRU cached block recycled). A high "
    "rate relative to hits means the pool is too small for the working "
    "set of shared prefixes.")
_M_STATE_SLOTS = _metrics.gauge(
    "hvd_tpu_gen_state_slots_in_use",
    "Per-sequence state slots held by running sequences (a model that "
    "declares CacheSpec.state: one slot a running sequence, as many "
    "slots as decode lanes). Zero for a model that declares none.")
_M_SNAPSHOT_SLOTS = _metrics.gauge(
    "hvd_tpu_gen_state_snapshot_slots_in_use",
    "State snapshots currently held (of HVD_TPU_GEN_STATE_SNAPSHOTS), "
    "each owned by one indexed prefix-cache block. Pinned at the pool "
    "size with a rising evicted count means hits are being cut short "
    "for want of snapshot slots, not of KV blocks.")
_M_SNAPSHOTS = _metrics.counter(
    "hvd_tpu_gen_state_snapshots_total",
    "State snapshots by event: 'taken' (a sequence's state copied into "
    "a snapshot slot at a prefill-chunk boundary), 'restored' (a "
    "snapshot copied into an admitted sequence's state slot on a prefix "
    "hit; a sequence with no hit starts from the null snapshot and "
    "counts nothing) and 'evicted' (a snapshot dropped with its block, "
    "or least recently used first when every slot was taken).",
    labels=("event",))
_M_SNAPSHOT_BYTES = _metrics.counter(
    "hvd_tpu_gen_state_snapshot_bytes_total",
    "Bytes of per-sequence state copied into ('taken') or out of "
    "('restored') a snapshot slot, or dropped with one ('evicted').",
    labels=("event",))


def count_snapshots(event: str, n: int, state_bytes: int) -> None:
    """``n`` snapshots ``event`` (taken | restored | evicted)."""
    if n:
        _M_SNAPSHOTS.labels(event=event).inc(n)
        _M_SNAPSHOT_BYTES.labels(event=event).inc(n * state_bytes)


class PerSequenceStateError(ValueError):
    """A path that cannot carry **per-sequence recurrent state**
    (``CacheSpec.state``) was asked to serve a model that declares it:
    the speculative verify step rolls back K/V rows only, a beam fork
    shares and copies blocks only, and the disagg wire ships blocks
    only. Each refuses instead of serving tokens from a wrong state."""


def refuse_state(model_cfg, what: str) -> None:
    """Raise :class:`PerSequenceStateError` if ``model_cfg`` declares
    per-sequence state; ``what`` names the path that cannot carry it."""
    state = getattr(model_cfg.cache_spec(), "state", ())
    if state:
        raise PerSequenceStateError(
            f"{what} cannot carry per-sequence recurrent state, and this "
            f"model's cache declares it (CacheSpec.state: "
            f"{', '.join(name for name, *_ in state)})")


def refuse_mtp(model_cfg) -> None:
    """Raise ``ValueError`` unless ``model_cfg`` declares an MTP module
    (:func:`mtp_depth`): ``spec_mode='mtp'`` drafts with it."""
    if mtp_depth(model_cfg) < 1:
        raise ValueError(
            f"spec_mode='mtp' drafts with the model's multi-token "
            f"prediction module, and {type(model_cfg).__name__} declares "
            f"none (num_nextn_predict_layers)")


class PlaneGroupsError(ValueError):
    """A path that keeps **one block list a sequence** was asked to
    serve a model whose cache declares plane groups
    (``CacheSpec.groups``: window planes beside full ones): the
    speculative verify step snapshots and rolls back one table's slots,
    a beam fork shares and copies one list of blocks, and the disagg
    wire ships one pool's blocks. Each refuses instead of serving from
    half a cache."""


def refuse_groups(model_cfg, what: str) -> None:
    """Raise :class:`PlaneGroupsError` if ``model_cfg`` declares plane
    groups; ``what`` names the path that keeps one block list."""
    groups = getattr(model_cfg.cache_spec(), "groups", ())
    if groups:
        raise PlaneGroupsError(
            f"{what} keeps one block list a sequence, and this model's "
            f"cache declares plane groups (CacheSpec.groups: "
            f"{', '.join(g.name for g in groups)})")


def chain_hash(parent: Optional[str], tokens: Sequence[int]) -> str:
    """Content key for one full KV block: commits to the parent block's
    hash (hence the entire token prefix) plus this block's tokens, so
    equal hashes imply bit-equal cache contents for the whole chain."""
    h = hashlib.sha1()
    h.update((parent or "").encode("ascii"))
    h.update(b"|")
    h.update(",".join(str(int(t)) for t in tokens).encode("ascii"))
    return h.hexdigest()


class BlocksExhaustedError(RuntimeError):
    """Not enough free KV blocks for an allocation. Internal to the
    generation plane: the scheduler answers it by preempting the
    youngest running sequence, never by wedging."""


class BlockAllocator:
    """Refcounting allocator over the KV block pool (block 0 reserved).

    Set-based accounting keeps every per-block operation O(1):
    ``_free_set`` mirrors the free stack, ``_ref`` maps each live block
    to its reference count (doubling as the owned set for double-free
    and foreign-id rejection), and ``_cached`` is an insertion-ordered
    dict whose order *is* the LRU eviction order of the cached-free
    pool. ``prefix_cache=None`` reads ``HVD_TPU_GEN_PREFIX_CACHE``;
    with the feature off, ``free`` recycles immediately and the index
    stays empty — the PR 9 allocator, with refcounts of 1.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 prefix_cache: Optional[bool] = None,
                 state_slots: int = 0, snapshot_slots: int = 0,
                 state_bytes: int = 0, group: str = "full",
                 window: Optional["BlockAllocator"] = None,
                 window_span: int = 0):
        if num_blocks < 2:
            raise ValueError(
                f"HVD_TPU_GEN_NUM_BLOCKS={num_blocks}: need at least 2 "
                f"(block 0 is the reserved null block)")
        if block_size < 1:
            raise ValueError(
                f"HVD_TPU_GEN_BLOCK_SIZE={block_size}: must be >= 1")
        if prefix_cache is None:
            prefix_cache = bool(
                _config.live_config().get(_config.GEN_PREFIX_CACHE))
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        #: usable blocks (block 0 excluded)
        self.capacity = self.num_blocks - 1
        self.prefix_cache = bool(prefix_cache)
        self._lock = _locks.lock("serving.generation.BlockAllocator._lock")
        # pop() hands out ascending ids — deterministic schedules make
        # the chaos drills replayable
        self._free_list = list(range(self.num_blocks - 1, 0, -1))
        self._free_set = set(self._free_list)
        self._ref: Dict[int, int] = {}
        # cached-free pool: block -> None, oldest-inserted first (LRU)
        self._cached: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._index: Dict[str, int] = {}    # content hash -> block
        self._hash_of: Dict[int, str] = {}  # indexed block -> its hash
        self._n_shared = 0                  # blocks with refcount >= 2
        #: blocks whose contents arrived over the disagg KV wire
        #: (register(..., remote=True)) rather than from local prefill;
        #: membership is sticky until the block recycles or evicts, so
        #: admission can attribute prefix-cache hits source=transfer
        self._remote: set = set()
        #: bumped by :meth:`reset_cache`; sequences record it so a block
        #: filled before a reset (stale params / zeroed pools) is never
        #: registered after one
        self.cache_gen = 0
        self.peak_in_use = 0
        # -- per-sequence state (module docstring): all empty, and every
        # path below unchanged, for a model that declares none
        #: slots of the state pools, one a running sequence
        self.state_slots = int(state_slots)
        #: usable snapshot slots (slot 0, the null snapshot, excluded)
        self.snapshot_slots = int(snapshot_slots) if state_slots else 0
        #: bytes of one sequence's state, for the byte counters
        self.state_bytes = int(state_bytes)
        self._state_free = list(range(self.state_slots - 1, -1, -1))
        self._state_held: set = set()
        self._snap_free = list(range(self.snapshot_slots, 0, -1))
        # content hash of the owning block -> snapshot slot, least
        # recently taken or restored first
        self._snap_of: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self.snapshot_peak = 0
        # -- plane groups (module docstring): this allocator's group,
        # and for a model with window planes their allocator and how
        # many blocks a window spans; None and 0 for every other model
        self.group = str(group)
        self.window = window
        self.window_span = int(window_span) if window is not None else 0
        #: the allocator the engine holds publishes the unlabelled
        #: gauges; a window group's only its own group's
        self._primary = True
        if window is not None:
            if state_slots:
                raise ValueError(
                    "a cache with both per-sequence state and plane groups "
                    "is not served: a prefix hit would need a snapshot at "
                    "the depth the window group cuts it to")
            if window.block_size != self.block_size or self.window_span < 1:
                raise ValueError(
                    f"window group: blocks of {window.block_size} against "
                    f"{self.block_size}, a window of {window_span} blocks")
            window._primary = False

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` cache slots."""
        return max(1, math.ceil(tokens / self.block_size))

    @property
    def free_blocks(self) -> int:
        """Truly-free blocks (cached-free blocks not included)."""
        with self._lock:
            return len(self._free_list)

    @property
    def cached_blocks(self) -> int:
        """Blocks parked in the cached-free pool (refcount 0, contents
        intact, evictable)."""
        with self._lock:
            return len(self._cached)

    @property
    def available_blocks(self) -> int:
        """Blocks an allocation could obtain right now: truly free plus
        evictable cached. The scheduler's admissibility checks use this
        so a prompt that fits only by evicting cached blocks is still
        admitted."""
        with self._lock:
            return len(self._free_list) + len(self._cached)

    @property
    def in_use(self) -> int:
        """Blocks referenced by at least one live sequence. Cached-free
        blocks are *not* in use — the leak checks throughout the tests
        and the benchmark rely on this returning 0 once every sequence
        has retired, cache or no cache."""
        with self._lock:
            return len(self._ref)

    def refcount(self, block: int) -> int:
        """Live references to ``block`` (0 for free or cached-free)."""
        with self._lock:
            return self._ref.get(block, 0)

    def stats(self) -> Dict[str, int]:
        """The ``{state: count}`` pool split published on the
        ``hvd_tpu_gen_kv_blocks`` gauge; the four states sum to
        :attr:`capacity`."""
        with self._lock:
            return self._stats_locked()

    def _stats_locked(self) -> Dict[str, int]:
        return {
            "free": len(self._free_list),
            "cached": len(self._cached),
            "private": len(self._ref) - self._n_shared,
            "shared": self._n_shared,
        }

    def _publish(self, in_use: int, stats: Dict[str, int]) -> None:
        # metric publication happens outside the lock: counts are
        # computed under it, cells are atomic
        _M_GROUP_BLOCKS.labels(group=self.group).set(in_use)
        if not self._primary:
            return
        _M_BLOCKS.set(in_use)
        for state, count in stats.items():
            _M_BLOCK_STATE.labels(state=state).set(count)

    def allocate(self, n: int) -> List[int]:
        """Hand out ``n`` blocks, all-or-nothing. Truly-free blocks are
        taken first; when the free list runs dry, cached-free blocks
        are evicted least-recently-used first (their index entries are
        dropped and ``hvd_tpu_gen_prefix_cache_evictions_total`` ticks).
        Raises :class:`BlocksExhaustedError` when free + cached cannot
        cover ``n`` — cached blocks are always sacrificed before the
        scheduler ever considers preempting a running sequence."""
        if n <= 0:
            return []
        evicted = dropped = 0
        with self._lock:
            if n > len(self._free_list) + len(self._cached):
                raise BlocksExhaustedError(
                    f"need {n} KV blocks, {len(self._free_list)} free + "
                    f"{len(self._cached)} cached "
                    f"(of {self.capacity} usable)")
            out = []
            for _ in range(n):
                if self._free_list:
                    b = self._free_list.pop()
                    self._free_set.discard(b)
                else:
                    b, _ = self._cached.popitem(last=False)
                    h = self._hash_of.pop(b)
                    if self._index.get(h) == b:
                        del self._index[h]
                        # a snapshot goes with the block that owns it
                        dropped += self._drop_snapshot_locked(h)
                    self._remote.discard(b)
                    evicted += 1
                self._ref[b] = 1
                out.append(b)
            in_use = len(self._ref)
            if in_use > self.peak_in_use:
                self.peak_in_use = in_use
            stats = self._stats_locked()
            held = len(self._snap_of)
        if evicted:
            _M_EVICTIONS.inc(evicted)
        if dropped:
            count_snapshots("evicted", dropped, self.state_bytes)
            _M_SNAPSHOT_SLOTS.set(held)
        self._publish(in_use, stats)
        return out

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per listed block. A block whose refcount
        reaches 0 parks in the cached-free pool if it is indexed (the
        sequence's blocks are parked tail-first, so LRU eviction eats a
        chain from its tail and the head prefix stays matchable) and is
        recycled otherwise. Releasing a free/cached block, the null
        block, or an id outside the pool raises — accounting bugs must
        fail the caller, not corrupt a stranger's cache."""
        with self._lock:
            counts = collections.Counter()
            for b in blocks:
                if not 1 <= b < self.num_blocks:
                    raise ValueError(
                        f"free of invalid KV block id {b} (pool is "
                        f"1..{self.num_blocks - 1})")
                counts[b] += 1
                if counts[b] > self._ref.get(b, 0):
                    raise ValueError(f"double free of KV block {b}")
            to_park = []
            for b in blocks:
                r = self._ref[b] - 1
                if r == 0:
                    del self._ref[b]
                    h = self._hash_of.get(b)
                    if h is not None and self._index.get(h) == b:
                        to_park.append(b)
                    else:
                        if h is not None:
                            del self._hash_of[b]
                        self._remote.discard(b)
                        self._free_list.append(b)
                        self._free_set.add(b)
                else:
                    self._ref[b] = r
                    if r == 1:
                        self._n_shared -= 1
            for b in reversed(to_park):
                self._cached[b] = None
            in_use = len(self._ref)
            stats = self._stats_locked()
        self._publish(in_use, stats)

    def release(self, blocks: List[int]) -> None:
        """:meth:`free` for blocks a *running* sequence gives back
        because its window has passed them, counted in
        ``hvd_tpu_gen_window_blocks_released_total``."""
        self.free(blocks)
        _M_WINDOW_RELEASED.inc(len(blocks))

    # -- prefix-cache surface -----------------------------------------

    def register(self, block: int, content_hash: str,
                 remote: bool = False) -> None:
        """Index a live *full* block under its content chain hash so
        future prompts can match it. First registration wins: a hash
        already indexed (or a block already hashed) is left alone, and
        the duplicate block simply recycles on release. No-op with the
        prefix cache off.

        ``remote=True`` marks the block as transfer-imported (its
        contents arrived over the disagg KV wire instead of local
        prefill); the flag sticks until the block recycles or evicts
        and drives the ``source=transfer`` split of the prefix-cache
        hit metric. A double-import of an already-indexed hash dedups
        exactly like a local duplicate — first registration wins, the
        second block recycles."""
        if not self.prefix_cache:
            return
        with self._lock:
            if block not in self._ref:
                raise ValueError(
                    f"register of KV block {block} with no live owner")
            if content_hash not in self._index and \
                    block not in self._hash_of:
                self._index[content_hash] = block
                self._hash_of[block] = content_hash
                if remote:
                    self._remote.add(block)

    def is_remote(self, block: int) -> bool:
        """True when ``block``'s contents arrived via KV transfer
        (``register(..., remote=True)``) and it has not recycled or
        evicted since."""
        with self._lock:
            return block in self._remote

    @property
    def remote_blocks(self) -> int:
        """Blocks currently carrying the transfer-imported mark (live
        or cached)."""
        with self._lock:
            return len(self._remote)

    def match_probe(self, hashes: Sequence[str]) -> Tuple[int, int]:
        """Side-effect-free length of the longest indexed prefix of
        ``hashes``: ``(matched_blocks, matched_cached)`` where the
        second count is how many of the matched blocks currently sit in
        the cached-free pool (they would leave it on a real
        :meth:`match`, so admissibility math must not double-count them
        as evictable)."""
        with self._lock:
            found = self._indexed_prefix_locked(hashes)
        found = found[:self._window_depth(hashes, len(found))]
        with self._lock:
            return len(found), sum(1 for b in found if b in self._cached)

    def _window_depth(self, hashes: Sequence[str], depth: int) -> int:
        """``depth`` cut to what the window group can continue from
        (no window group: as it is). Asked outside this allocator's
        lock: the window group's is its own."""
        if self.window is None:
            return depth
        return self.window.covered_depth(hashes[:depth], self.window_span)

    def covered_depth(self, hashes: Sequence[str], span: int) -> int:
        """The deepest ``d <= len(hashes)`` at which this (window)
        group still holds what the token after block ``d - 1`` reads:
        the ``span`` blocks before the boundary, or all ``d`` where the
        prefix is shorter. 0 where no boundary is covered."""
        with self._lock:
            run, best = 0, 0
            for d, h in enumerate(hashes, 1):
                run = run + 1 if h in self._index else 0
                if run >= min(d, span):
                    best = d
            return best

    def match_tail(self, hashes: Sequence[str], span: int) -> List[int]:
        """Attach this (window) group's blocks of a hit that the first
        group's :meth:`match` cut to ``hashes``: the last ``span`` of
        them (refcounts bumped, cached blocks revived), 0 in the place
        of every earlier one, which the sequence never holds. One entry
        a hash, in chain order."""
        skip = max(0, len(hashes) - span)
        held = self.match(hashes[skip:])
        if len(held) != len(hashes) - skip:
            self.free(held)
            raise ValueError(
                f"window group: {len(held)} of {len(hashes) - skip} blocks "
                f"of a covered hit are indexed")
        return [0] * skip + held

    def _indexed_prefix_locked(self, hashes: Sequence[str]) -> List[int]:
        """Blocks of the longest indexed prefix of ``hashes`` that a
        sequence may attach: for a model with per-sequence state, cut
        back to the deepest block that owns a snapshot (none: nothing),
        since the blocks past it cannot be continued from."""
        found: List[int] = []
        deepest = 0
        for h in hashes:
            b = self._index.get(h)
            if b is None:
                break
            found.append(b)
            if h in self._snap_of:
                deepest = len(found)
        return found[:deepest] if self.state_slots else found

    def match(self, hashes: Sequence[str]) -> List[int]:
        """Attach the longest indexed prefix of ``hashes``: cached-free
        blocks revive with refcount 1, live blocks bump their refcount
        (becoming shared). Returns the matched block ids in chain
        order; the caller owns one reference to each. For a model with
        per-sequence state the prefix ends at the deepest block that
        owns a snapshot (:meth:`snapshot_of` the last block returned);
        with a window group at the deepest boundary that group covers
        (:meth:`covered_depth`; the caller attaches its blocks with
        ``window.match_tail``)."""
        out: List[int] = []
        if not self.prefix_cache:
            return out
        with self._lock:
            found = self._indexed_prefix_locked(hashes)
        found = found[:self._window_depth(hashes, len(found))]
        with self._lock:
            for b in found:
                if b in self._cached:
                    del self._cached[b]
                    self._ref[b] = 1
                else:
                    r = self._ref[b] + 1
                    self._ref[b] = r
                    if r == 2:
                        self._n_shared += 1
                out.append(b)
            in_use = len(self._ref)
            if in_use > self.peak_in_use:
                self.peak_in_use = in_use
            stats = self._stats_locked()
        if out:
            self._publish(in_use, stats)
        return out

    def share(self, blocks: Sequence[int]) -> None:
        """Bump the refcount of already-live blocks — the beam-search
        fork path: a child beam attaches its parent's full prefix
        blocks instead of copying them, exactly like a prefix-cache
        :meth:`match` except the blocks are named directly (beams of
        one request share blocks whether or not the content index is
        enabled). Sharing a free, cached, or null block raises — only a
        live owner can be forked from."""
        bl = list(blocks)
        with self._lock:
            for b in bl:
                if b not in self._ref:
                    raise ValueError(
                        f"share of KV block {b} with no live owner")
            for b in bl:
                r = self._ref[b] + 1
                self._ref[b] = r
                if r == 2:
                    self._n_shared += 1
            in_use = len(self._ref)
            if in_use > self.peak_in_use:
                self.peak_in_use = in_use
            stats = self._stats_locked()
        self._publish(in_use, stats)

    def reset_cache(self) -> None:
        """Drop the whole content index and recycle every cached-free
        block. Called when cache *contents* stop being trustworthy —
        a params hot-swap or a device-pool rebuild — and bumps
        :attr:`cache_gen` so blocks filled under the old contents are
        never registered under the new ones."""
        with self._lock:
            for b in self._cached:
                self._free_list.append(b)
                self._free_set.add(b)
            self._cached.clear()
            self._index.clear()
            self._hash_of.clear()
            self._remote.clear()
            self.cache_gen += 1
            dropped = len(self._snap_of)
            self._snap_free.extend(self._snap_of.values())
            self._snap_of.clear()
            in_use = len(self._ref)
            stats = self._stats_locked()
        if dropped:
            count_snapshots("evicted", dropped, self.state_bytes)
            _M_SNAPSHOT_SLOTS.set(0)
        self._publish(in_use, stats)

    # -- per-sequence state ----------------------------------------------

    def take_state_slot(self) -> int:
        """A state slot for a sequence entering the running set. There
        are as many as decode lanes, so a sequence that found a lane
        finds a slot; the caller zeroes or restores it."""
        with self._lock:
            if not self._state_free:
                raise RuntimeError(
                    f"no free state slot (of {self.state_slots}): more "
                    f"running sequences than decode lanes")
            slot = self._state_free.pop()
            self._state_held.add(slot)
            held = len(self._state_held)
        _M_STATE_SLOTS.set(held)
        return slot

    def release_state_slot(self, slot: int) -> None:
        with self._lock:
            if slot not in self._state_held:
                raise ValueError(f"release of state slot {slot}, not held")
            self._state_held.discard(slot)
            self._state_free.append(slot)
            held = len(self._state_held)
        _M_STATE_SLOTS.set(held)

    @property
    def state_slots_in_use(self) -> int:
        with self._lock:
            return len(self._state_held)

    @property
    def snapshot_slots_in_use(self) -> int:
        with self._lock:
            return len(self._snap_of)

    def snapshots_orphaned(self) -> int:
        """Snapshot slots that no indexed block owns, or that are
        neither held nor free: 0 unless the accounting leaked."""
        with self._lock:
            lost = self.snapshot_slots - len(self._snap_of) \
                - len(self._snap_free)
            return lost + sum(1 for h in self._snap_of
                              if h not in self._index)

    def claim_snapshot(self, block: int) -> Optional[int]:
        """A snapshot slot owned by indexed block ``block``, for the
        state its sequence has after the block's last token; the caller
        copies the state in. None where the block is not the indexed
        holder of its hash (the prefix cache is off, or an equal block
        was registered first) or already owns one. With every slot
        taken the least recently used snapshot is evicted."""
        evicted = 0
        with self._lock:
            h = self._hash_of.get(block)
            if not self.snapshot_slots or h is None \
                    or self._index.get(h) != block:
                return None
            if h in self._snap_of:
                self._snap_of.move_to_end(h)
                return None
            if not self._snap_free:
                _, freed = self._snap_of.popitem(last=False)
                self._snap_free.append(freed)
                evicted = 1
            slot = self._snap_of[h] = self._snap_free.pop()
            held = len(self._snap_of)
            self.snapshot_peak = max(self.snapshot_peak, held)
        count_snapshots("evicted", evicted, self.state_bytes)
        count_snapshots("taken", 1, self.state_bytes)
        _M_SNAPSHOT_SLOTS.set(held)
        return slot

    def snapshot_of(self, block: int) -> int:
        """The snapshot slot ``block`` owns (0, the null snapshot, for
        none), marked recently used: what an admission restores after
        :meth:`match` returned ``block`` last."""
        with self._lock:
            h = self._hash_of.get(block)
            if h is None or h not in self._snap_of:
                return 0
            self._snap_of.move_to_end(h)
            return self._snap_of[h]

    def drop_snapshot(self, block: int) -> bool:
        """Evict the snapshot ``block`` owns, keeping the block."""
        with self._lock:
            h = self._hash_of.get(block)
            dropped = self._drop_snapshot_locked(h) if h else 0
            held = len(self._snap_of)
        count_snapshots("evicted", dropped, self.state_bytes)
        _M_SNAPSHOT_SLOTS.set(held)
        return bool(dropped)

    def _drop_snapshot_locked(self, content_hash: str) -> int:
        slot = self._snap_of.pop(content_hash, None)
        if slot is None:
            return 0
        self._snap_free.append(slot)
        return 1


#: a TPU's lane count: the minor axis of an array is tiled in 128s
_LANES = 128


def _row(width: int) -> int:
    """A pool row for ``width`` values: the next multiple of 128."""
    return -(-int(width) // _LANES) * _LANES


def make_pools(model_cfg, num_blocks: int, block_size: int,
               state_slots: int = 0):
    """Zeroed cache pools for ``model_cfg``, read off its declaration
    (``model_cfg.cache_spec()``, a
    :class:`~horovod_tpu.models.transformer.CacheSpec`): a tuple with
    one ``(planes, num_blocks, block_size, row)`` array for each
    declared row, in the declared dtype. A pool row holds what one
    token leaves in one plane (for
    :class:`~horovod_tpu.models.transformer.TransformerConfig`: its K,
    or its V, ``heads * head_dim`` values), then zeros up to a multiple
    of 128.

    The shape is chosen for the layout a TPU gives it. The device tiles
    an array's two minor axes ``(8, 128)`` and stores it in whichever
    axis order pads least. ``(..., 25, 64)`` would pad to ``(32, 128)``,
    so a ``(L, N, bs, heads, head_dim)`` pool is stored blocks-minor,
    and so is ``(L, N, bs, 1600)`` as soon as ``N`` is a multiple of
    128 — and the paged programs then relayout every slab they scatter
    into or gather from, or the whole pool. A row that is a multiple of
    128 pads nothing, row-major wins for any ``N``, and a token's row is
    contiguous; the device would have padded 1600 to 1664 itself.

    For a model that declares per-sequence state the tuple goes on with
    one ``(planes, state_slots, *shape)`` array for each declared state,
    in its own dtype (:func:`make_state_pools`): the programs thread,
    donate and update them in place like the row pools.

    For a model that declares plane groups ``num_blocks`` is a tuple,
    one pool size a group, and the pools come a group at a time: the
    first group's rows, ``(group.planes, its blocks, block_size, row)``
    each, then the next group's."""
    import jax.numpy as jnp
    spec = model_cfg.cache_spec()
    groups = spec.plane_groups()
    counts = tuple(num_blocks) if isinstance(num_blocks, (tuple, list)) \
        else (num_blocks,)
    if len(counts) != len(groups):
        raise ValueError(
            f"{len(counts)} pool sizes for a cache of {len(groups)} plane "
            f"groups ({', '.join(g.name for g in groups)})")
    return tuple(
        jnp.zeros((g.planes, n, block_size, _row(width)), spec.dtype)
        for g, n in zip(groups, counts) for _, width in spec.rows) \
        + make_state_pools(model_cfg, state_slots)


def make_state_pools(model_cfg, slots: int):
    """Zeroed pools of ``slots`` entries for each per-sequence state
    ``model_cfg`` declares: the tail of :func:`make_pools` (a running
    sequence's slot) and, with ``snapshots + 1`` entries, the snapshot
    pools (entry 0 the null snapshot). ``()`` for a model without."""
    import jax.numpy as jnp
    spec = model_cfg.cache_spec()
    if spec.state and slots < 1:
        raise ValueError(
            f"the model's cache declares per-sequence state "
            f"({', '.join(name for name, *_ in spec.state)}): its pools "
            f"need at least one slot, got {slots}")
    return tuple(jnp.zeros((planes, slots) + tuple(shape), dtype)
                 for _, planes, shape, dtype in spec.state)


def state_bytes(model_cfg) -> int:
    """Bytes of per-sequence state one sequence holds (one snapshot)."""
    import jax.numpy as jnp
    return sum(planes * math.prod(shape) * jnp.dtype(dtype).itemsize
               for _, planes, shape, dtype in model_cfg.cache_spec().state)


@functools.lru_cache(maxsize=None)
def build_state_copy_program():
    """``(dst_pools, src_pools, dst_slot, src_slot) -> dst_pools``:
    entry ``src_slot`` of every state pool in ``src_pools`` copied over
    entry ``dst_slot`` of its partner in ``dst_pools`` (donated, so in
    place). One function serves both directions: the state pools and
    the snapshot pools differ only in how many entries they have."""
    import jax

    def _copy_state(dst, src, dst_slot, src_slot):
        return tuple(d.at[:, dst_slot].set(s[:, src_slot])
                     for d, s in zip(dst, src))

    return jax.jit(_copy_state, donate_argnums=(0,))


def block_bytes(model_cfg, block_size: int, group: Optional[int] = None) -> int:
    """Bytes of cache one block holds: every declared row, padded as
    :func:`make_pools` allocates it, in every plane (per-sequence state
    is no part of a block: :func:`state_bytes`); with ``group`` in the
    planes of that plane group alone, a block of its pools."""
    import jax.numpy as jnp
    spec = model_cfg.cache_spec()
    planes = spec.planes if group is None \
        else spec.plane_groups()[group].planes
    return (planes * block_size * jnp.dtype(spec.dtype).itemsize
            * sum(_row(width) for _, width in spec.rows))


def gather_blocks(pools, blocks: Sequence[int]):
    """Materialize the contents of pool ``blocks`` on the host for the
    disagg KV wire: one ``(planes, len(blocks), block_size, row)``
    array for each pool, in the pools' order and dtype. Must run on the
    scheduler thread (the pools are donated device buffers the
    scheduler owns)."""
    idx = list(blocks)
    return tuple(np.asarray(p[:, idx]) for p in pools)


def scatter_blocks(pools, blocks: Sequence[int], data):
    """Write transferred block contents (``data``: one array a pool, as
    :func:`gather_blocks` gives them) into pool slots ``blocks``;
    returns the new pools (functional ``.at[].set``, so an in-flight
    decode step's buffers are untouched). Scheduler-thread only, like
    :func:`gather_blocks`."""
    idx = list(blocks)
    if len(data) != len(pools):
        raise ValueError(
            f"{len(data)} transferred rows for a cache of {len(pools)}")
    return tuple(p.at[:, idx].set(np.asarray(d, dtype=p.dtype))
                 for p, d in zip(pools, data))


def _paged_apply(model, params, tokens, cache, logits_at=None, **kwargs):
    """``model.apply`` on the paged path: ``(logits, cache, stats)``, or
    with ``kwargs`` that make the model return more (an MTP model's
    ``with_hidden``) ``(logits, cache, *more, stats)``; ``kwargs`` also
    carries ``method`` (:meth:`~horovod_tpu.models.Glm47Flash.mtp_forward`).
    ``stats`` is ``()`` for a model that declares no experts, so its
    programs return what they always did; for one that does
    (``model.cfg.held_experts``) it is ``(counts,)``: the id of the
    first expert the model holds, then the layers' int32 routing counts
    summed. The sampling programs return it beside their tokens, and the
    vector says whose counts they are."""
    if logits_at is not None:
        kwargs["logits_at"] = logits_at
    if not getattr(model.cfg, "held_experts", None):
        return (*model.apply(params, tokens, cache=cache, **kwargs), ())
    import jax

    from ...parallel.moe import STATS_COLLECTION
    out, sown = model.apply(
        params, tokens, cache=cache, mutable=[STATS_COLLECTION], **kwargs)
    counts = sum(jax.tree_util.tree_leaves(sown[STATS_COLLECTION]))
    first = jax.numpy.asarray(model.cfg.held_experts[:1], counts.dtype)
    return (*out, (jax.numpy.concatenate([first, counts]),))


def _mtp_apply(model, params, tokens, hidden, cache, logits_at, stats):
    """The MTP module's pass (:func:`_paged_apply` of the model's
    ``mtp_forward``) over ``tokens`` (``t_{i+1}`` at the column of
    position ``i``) and ``hidden``, the main pass's final hidden states:
    ``(greedy draft (B,) int32, cache, stats)``, its routing counts
    added into the main pass's ``stats``."""
    import jax.numpy as jnp

    logits, cache, more = _paged_apply(
        model, params, tokens, cache, logits_at=logits_at, hidden=hidden,
        method=type(model).mtp_forward)
    stats = tuple(a.at[1:].add(b[1:]) for a, b in zip(stats, more))
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache, stats


def mtp_depth(model_cfg) -> int:
    """Multi-token prediction modules the model declares
    (``num_nextn_predict_layers``): what ``spec_mode='mtp'`` drafts
    with, a token a module; 0 for a model without."""
    return int(getattr(model_cfg, "num_nextn_predict_layers", 0) or 0)


def _query_rows(model, chunk: int):
    """Query rows the model's paged attention brings to
    :mod:`horovod_tpu.ops.paged_attention` for a ``chunk``-column step
    (``cfg.paged_query_rows``), or None for a model whose attention
    never calls it. The decode-side builders hang it on their program
    as ``query_rows`` for :func:`reads_live_blocks`."""
    rows = getattr(model.cfg, "paged_query_rows", None)
    return None if rows is None else int(rows(chunk))


def reads_live_blocks(program, pools) -> bool:
    """Whether ``program``'s attention over ``pools`` runs on the paged
    kernel, which reads the blocks the live lanes hold and no others
    (else: the gather path, every table). The forward's own rule
    (:func:`~horovod_tpu.ops.paged_attention.kernel_applies`) on the
    shapes this program was built for, so the scheduler counts what the
    program does without lowering it."""
    rows = getattr(program, "query_rows", None)
    pool = pools[0]
    return rows is not None and paged_attention.kernel_applies(
        rows, pool.shape[2], pool.shape[3], pool.dtype)


def prefill_keys_walked(program, chunk: int, length: int, live: int,
                        block_size: int, max_blocks: int) -> int:
    """Slots of a lane's block table that one attention of the prefill
    ``program`` reads for a chunk ``chunk`` columns wide, ``live`` of
    them live, after ``length`` tokens: the model's own arithmetic
    (``cfg.prefill_keys_walked``, the forward's rule and its walk, hung
    on the program by its builder), so the scheduler counts what the
    program does without lowering it; the whole table for a model whose
    prefill gathers it whatever the sequence holds."""
    walked = getattr(program, "keys_walked", None)
    if walked is None:
        return max_blocks * block_size
    return int(walked(chunk, length, live, block_size, max_blocks))


@functools.lru_cache(maxsize=8)
def build_program(model):
    """The raw-logits jitted incremental forward.

    ``(params, PagedCache, tokens) -> (logits, PagedCache)``; the cache
    argument is donated and the forward threads one live pool through
    its layers (each scatters into and gathers from its own plane of
    it), so XLA updates the pools in place: the compiled program
    aliases them input to output and copies neither a layer's slab nor
    a pool (``tests/test_paged_inplace.py`` holds all five programs to
    that). Called with
    ``tokens`` of shape ``(1, prefill_chunk)`` it is the prefill
    program; with ``(max_seqs, DECODE_WIDTH)`` it is the decode
    program — two compilations of one function. Memoized on the model
    (flax modules hash by configuration), so engine restarts and tests
    don't recompile identical programs.

    The scheduler's hot path no longer runs this program — it drives
    :func:`build_prefill_program` / :func:`build_decode_program`, which
    sample on device and never ship logits to the host. This one stays
    as the reference surface: the parity tests pin the sampling
    programs' greedy tokens against its host-side ``argmax``.
    """
    import jax

    def _paged_forward(params, cache, tokens):
        return model.apply(params, tokens, cache=cache)

    return jax.jit(_paged_forward, donate_argnums=(1,))


# -- on-device sampling ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SampleParams:
    """Per-lane sampling controls, resident on device.

    ``temperature`` ``(B,)`` float32 — ``<= 0`` selects greedy argmax
    (bit-identical to host ``np.argmax`` of the raw logits).
    ``top_k`` ``(B,)`` int32 — keep the k highest-scoring tokens
    (``<= 0`` disables). ``top_p`` ``(B,)`` float32 — nucleus mass
    (``>= 1`` disables; the top token always survives). ``key``
    ``(B, 2)`` uint32 — the per-request PRNG key; every emission folds
    the emitted-token ordinal into it (``jax.random.fold_in``), so a
    continuation is a pure function of (seed, position) and the
    preemption-recompute path replays the identical tokens. ``emitted``
    ``(B,)`` int32 — that ordinal (== tokens generated so far).
    """

    temperature: Any
    top_k: Any
    top_p: Any
    key: Any
    emitted: Any


@dataclasses.dataclass(frozen=True)
class DecodeState:
    """The device-resident decode loop state, one row per batch lane.

    The decode program consumes and re-emits it (donated), feeding each
    lane's sampled token back as the next input in place: ``tokens``
    ``(B,)`` int32 next-input ids, ``lengths`` ``(B,)`` int32 cache
    lengths, ``live`` ``(B,)`` int32 lane-occupied mask, ``remaining``
    ``(B,)`` int32 tokens still to emit, ``eos`` ``(B,)`` int32 EOS id
    (-1 = none), and the :class:`SampleParams`. Retirement (EOS or
    ``max_tokens``) is decided *inside* the program — a retired lane's
    ``live`` drops to 0 on device, so a speculatively enqueued next
    step routes its writes to the null block with no host round-trip.
    The host only rebuilds and re-uploads this state when batch
    membership changes (admit/retire/preempt), keyed by a batch epoch.
    """

    tokens: Any
    lengths: Any
    live: Any
    remaining: Any
    eos: Any
    sample: SampleParams
    #: ``(B,)`` int32, the MTP verify step's draft for each lane (the
    #: token after ``tokens``, from the MTP module's logits of the step
    #: that emitted ``tokens``); None for every other program
    draft: Any = None


def _register_pytrees():
    import jax
    jax.tree_util.register_dataclass(
        SampleParams,
        data_fields=["temperature", "top_k", "top_p", "key", "emitted"],
        meta_fields=[])
    jax.tree_util.register_dataclass(
        DecodeState,
        data_fields=["tokens", "lengths", "live", "remaining", "eos",
                     "sample", "draft"],
        meta_fields=[])


_register_pytrees()


#: ``hvd_tpu_gen_sample_steps_total``'s ``cut`` by (a sampling lane has
#: ``top_k > 0``) + 2 x (a sampling lane has ``top_p < 1``)
SAMPLE_CUTS = ("none", "top_k", "top_p", "both")


def _cuts_asked(xp, temperature, top_k, top_p):
    """``(a lane samples, a sampling lane has top_k > 0, one has
    top_p < 1)`` of one batch: what decides which of
    :func:`sample_tokens`' branches run. One predicate for the program
    (``xp`` is ``jax.numpy``) and for the scheduler's counter
    (``numpy``, :func:`sample_cut`)."""
    sampled = ~(temperature <= 0.0)
    return (xp.any(sampled), xp.any(sampled & (top_k > 0)),
            xp.any(sampled & (top_p < 1.0)))


def sample_cut(temperature, top_k, top_p) -> str:
    """The ``cut`` label of ``hvd_tpu_gen_sample_steps_total`` for a
    dispatch whose :class:`SampleParams` hold these host vectors:
    ``greedy`` (the program takes ``argmax`` and nothing else), or which
    threshold searches it runs (:data:`SAMPLE_CUTS`)."""
    sampled, k, p = _cuts_asked(np, np.asarray(temperature),
                                np.asarray(top_k), np.asarray(top_p))
    return SAMPLE_CUTS[int(k) + 2 * int(p)] if sampled else "greedy"


def sample_tokens(logits, sample: SampleParams):
    """Select one token per row from ``(B, vocab)`` logits, on device.

    Greedy rows (``temperature <= 0``) take ``argmax``; sampled rows
    scale by temperature, apply top-k then top-p restriction, and draw
    categorically under the row's folded PRNG key. Returns
    ``(token (B,) int32, logprob (B,) float32)`` — the logprob is under
    the *unmodified* distribution, so observability reads the model's
    actual confidence, not the truncated one.

    Neither restriction sorts: each threshold is found by a bitwise
    search over the value's bit pattern that counts (top-k) or sums
    (top-p) what lies at or above a candidate, and a restriction no
    sampling lane asked for is skipped (:func:`_cuts_asked`).
    """
    import jax

    with jax.named_scope("sample_tokens"):
        return _sample_tokens(logits, sample)


def _ordered_int(x):
    """float32 <-> an int32 whose signed order is the floats' order
    (``-0.0`` just below ``+0.0``). Its own inverse."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7fffffff, bits)


def _largest_accepted(accept, rows: int):
    """Per row the largest int32 ``t`` with ``accept(t)``, for an
    ``accept`` that holds at ``INT32_MIN`` and, once false, stays false
    as ``t`` grows: ``t`` is built a bit a pass from the sign down, each
    pass keeping the bit if ``accept`` still holds with it set."""
    import jax
    import jax.numpy as jnp

    def _pass(i, t):
        # int32 wraps: INT32_MIN + 2**31 is 0, the sign bit's candidate
        cand = t + jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(accept(cand), cand, t)

    return jax.lax.fori_loop(
        0, 32, _pass,
        jnp.full((rows,), jnp.iinfo(jnp.int32).min, jnp.int32))


def _kth_largest(x, k):
    """Row ``i``'s ``k[i]``-th largest value of ``x`` ``(B, V)``
    float32, exactly (ties are one value): the largest ``t`` that at
    least ``k`` values reach."""
    import jax
    import jax.numpy as jnp

    key = _ordered_int(x)
    t = _largest_accepted(
        lambda t: jnp.sum(key >= t[:, None], axis=-1, dtype=jnp.int32) >= k,
        x.shape[0])
    return jax.lax.bitcast_convert_type(_ordered_int(t), jnp.float32)


def _nucleus_threshold(probs, top_p):
    """Row ``i``'s largest ``t`` with ``sum(probs[probs >= t]) >=
    top_p[i]``, never above the row's maximum: ``probs >= t`` is the
    smallest set of the most probable tokens that holds ``top_p`` mass
    (whole ties), and the top token is always in it. Probabilities are
    non-negative, so their bit patterns order as integers."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(probs, jnp.int32)
    t = _largest_accepted(
        lambda t: jnp.sum(jnp.where(bits >= t[:, None], probs, 0.0),
                          axis=-1) >= top_p,
        probs.shape[0])
    return jax.lax.bitcast_convert_type(
        jnp.minimum(t, jnp.max(bits, axis=-1)), jnp.float32)


def _restrict(scaled, sample: SampleParams, top_k_asked, top_p_asked):
    """``scaled`` ``(B, V)`` with ``-inf`` where a row's top-k then
    top-p restriction drops the token; a restriction nobody asked for
    is not computed (it would drop nothing)."""
    import jax
    import jax.numpy as jnp

    vocab = scaled.shape[-1]

    def _top_k(x):
        # threshold at the k-th highest score (k <= 0 keeps all)
        k_eff = jnp.clip(jnp.where(sample.top_k <= 0, vocab,
                                   sample.top_k), 1, vocab)
        kth = _kth_largest(x, k_eff)
        return jnp.where(x < kth[:, None], -jnp.inf, x)

    def _top_p(x):
        # the smallest set of the most probable survivors holding >= p
        # mass; the top token always stays
        probs = jax.nn.softmax(x, axis=-1)
        thresh = _nucleus_threshold(probs, sample.top_p)
        return jnp.where(
            (sample.top_p < 1.0)[:, None] & (probs < thresh[:, None]),
            -jnp.inf, x)

    limited = jax.lax.cond(top_k_asked, _top_k, lambda x: x, scaled)
    return jax.lax.cond(top_p_asked, _top_p, lambda x: x, limited)


def _sample_tokens(logits, sample: SampleParams):
    import jax
    import jax.numpy as jnp

    greedy = sample.temperature <= 0.0
    argmax_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled, top_k_asked, top_p_asked = _cuts_asked(
        jnp, sample.temperature, sample.top_k, sample.top_p)

    def _draw(_):
        # float32 whatever the caller's dtypes: the searches read its bits
        scaled = (logits / jnp.where(greedy, 1.0, sample.temperature)
                  [:, None]).astype(jnp.float32)
        limited = _restrict(scaled, sample, top_k_asked, top_p_asked)
        keys = jax.vmap(jax.random.fold_in)(sample.key, sample.emitted)
        drawn = jax.vmap(jax.random.categorical)(keys, limited)
        return drawn.astype(jnp.int32)

    # all-greedy batches skip the restriction + categorical draw at
    # runtime; sampled lanes run the identical ops either way, so the
    # per-seed draw is unchanged by the branch
    drawn = jax.lax.cond(sampled, _draw, lambda _: argmax_tok, operand=None)
    token = jnp.where(greedy, argmax_tok, drawn)
    logprob = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), token[:, None], axis=-1)[:, 0]
    return token, logprob


@functools.lru_cache(maxsize=8)
def build_prefill_program(model, mtp: bool = False):
    """The sampling prefill program:
    ``(params, PagedCache, tokens, SampleParams) ->
    (token (B,), logprob (B,), PagedCache)`` (and, last, the routing
    counts of a model with experts: :func:`_paged_apply`).

    One chunk of prompt K/V lands in the cache and the *final* live
    position's next token is sampled on device — the host never sees
    chunk logits, so intermediate chunks don't even synchronize. The
    cache is donated; ``tokens`` is ``(1, prefill_chunk)``.

    ``mtp`` (``spec_mode='mtp'``, a model with an MTP module): the
    program takes one more argument, ``lookahead`` ``(B,)`` int32, the
    token after the chunk's last live one where the host knows it (the
    prompt's next token, or a recomputed sequence's next input) and -1
    where it is the token sampled here, and returns ``draft`` ``(B,)``
    after the cache. The MTP module runs over the chunk's positions
    with the tokens shifted by one (``lookahead`` at the last live
    column), writing its plane's row at each of them, and ``draft`` is
    its greedy token two places past the chunk's last: after the last
    chunk, the verify step's first draft.
    """
    import jax
    import jax.numpy as jnp

    def _prefill(params, cache, tokens, sample):
        at = jnp.maximum(cache.live - 1, 0).astype(jnp.int32)
        logits, cache, stats = _paged_apply(model, params, tokens, cache,
                                            logits_at=at)
        token, logprob = sample_tokens(logits, sample)
        return (token, logprob, cache, *stats)

    def _prefill_mtp(params, cache, tokens, sample, lookahead):
        at = jnp.maximum(cache.live - 1, 0).astype(jnp.int32)
        logits, cache, hidden, stats = _paged_apply(
            model, params, tokens, cache, logits_at=at, with_hidden=True)
        token, logprob = sample_tokens(logits, sample)
        rows = jnp.arange(tokens.shape[0])
        shifted = jnp.roll(tokens, -1, axis=1).at[rows, at].set(
            jnp.where(lookahead >= 0, lookahead, token))
        draft, cache, stats = _mtp_apply(model, params, shifted, hidden,
                                         cache, at, stats)
        return (token, logprob, cache, draft, *stats)

    if mtp:
        refuse_mtp(model.cfg)
    program = jax.jit(_prefill_mtp if mtp else _prefill, donate_argnums=(1,))
    program.keys_walked = getattr(model.cfg, "prefill_keys_walked", None)
    program.attention_reads = getattr(model.cfg, "attention_reads", None)
    return program


@functools.lru_cache(maxsize=8)
def build_decode_program(model, decode_width: int = 2):
    """The device-resident decode step:
    ``(params, pools, tables, DecodeState) ->
    (pools, DecodeState, token (B,), logprob (B,))`` (and, last, the
    routing counts of a model with experts). ``pools`` is the tuple
    :func:`make_pools` returned, whatever the model declared.

    One fixed-shape step over every lane: write K/V at each live lane's
    cache position (dead lanes route to the null block), sample the
    next token, and advance the state *in place* — sampled tokens feed
    back as the next inputs, lengths/remaining/emitted tick forward,
    and lanes hitting EOS or ``max_tokens`` drop their own ``live``
    flag so a speculatively enqueued next step is already harmless.
    The pools and the state are donated (the persistent device
    buffers); ``tables`` is NOT — the host re-uploads it only when a
    block table actually changed, and block growth alone never forces
    a pipeline flush. The per-step device->host transfer is the
    ``(B,)`` token and logprob vectors — never logits.
    """
    import jax
    import jax.numpy as jnp

    def _decode(params, pools, tables, state):
        B = state.tokens.shape[0]
        tokens = jnp.zeros((B, decode_width), jnp.int32)
        tokens = tokens.at[:, 0].set(state.tokens)
        live = jnp.minimum(state.live, 1).astype(jnp.int32)
        cache = PagedCache(pools, tables, state.lengths, live)
        logits, cache, stats = _paged_apply(
            model, params, tokens, cache,
            logits_at=jnp.zeros((B,), jnp.int32))
        sampled, logprob = sample_tokens(logits, state.sample)
        alive = live > 0
        token = jnp.where(alive, sampled, state.tokens)
        retired = alive & (((state.eos >= 0) & (token == state.eos))
                           | (state.remaining <= 1))
        new_state = DecodeState(
            tokens=token,
            lengths=state.lengths + live,
            live=jnp.where(retired, 0, live),
            remaining=state.remaining - live,
            eos=state.eos,
            sample=dataclasses.replace(
                state.sample, emitted=state.sample.emitted + live))
        return (cache.pools, new_state, token, logprob, *stats)

    program = jax.jit(_decode, donate_argnums=(1, 3))
    program.query_rows = _query_rows(model, decode_width)
    program.attention_reads = getattr(model.cfg, "attention_reads", None)
    return program


@functools.lru_cache(maxsize=8)
def build_verify_program(model, spec_tokens: int, mtp: bool = False):
    """The speculative-decoding verify step:
    ``(params, pools, tables, DecodeState, draft (B, S),
    draft_len (B,)) -> (pools, DecodeState, pred (B, S+1),
    logprob (B, S+1), n_emit (B,))`` (and, last, the routing counts of
    a model with experts, drafted positions included: they were
    computed).

    One paged forward scores a lane's current input token plus up to
    ``S = spec_tokens`` drafted continuations in a single chunk of
    static width ``S+1`` — the memory-bound decode step's weight read
    amortized over every position. Per position ``i`` the program
    recomputes exactly the token the plain decoder would have produced
    there (:func:`sample_tokens` under the deterministic
    ``fold_in(key, emitted + i)`` draw — greedy AND seeded sampling),
    accepts the longest drafted prefix matching those tokens, and emits
    one bonus token past it (the correction at the first mismatch, or
    the free extra token when every draft held). Output is therefore
    BIT-IDENTICAL to non-speculative decode, logprobs included; the
    draft only decides how many steps it took.

    Cache discipline: the forward writes K/V for every chunk position,
    because position ``i``'s logits must attend to drafts ``< i``.
    Rejected positions are then *rolled back* — their slots' original
    contents (snapshotted before the forward) are scattered back, with
    the restore writes of *committed* positions routed to the null
    block — so the pools end the step exactly as if only the accepted
    tokens had ever been written. Dead lanes' writes route to the null
    block throughout, as in the decode program. A lane with
    ``draft_len == 0`` degrades to precisely the plain decode step
    (accept 0 drafts, emit 1 token).

    The pools and the state are donated; ``tables`` is not. The
    per-step transfer is ``(B, S+1)`` tokens + logprobs plus the
    ``(B,)`` accept count — still never logits.

    ``mtp`` (``spec_mode='mtp'``, ``spec_tokens`` the model's MTP depth,
    1): the draft is the device's own, ``DecodeState.draft``, and the
    program is ``(params, pools, tables, DecodeState) -> (pools,
    DecodeState, pred, logprob, n_emit, draft (B,))``: ``draft`` is the
    new state's draft where a lane was live and -1 elsewhere, an array
    of its own, so the host may read it after the state is donated to
    the next step. After the accept rule the MTP
    module runs over the committed positions ``L .. L + n_emit - 1``,
    the tokens shifted by one (``pred``: the token each position
    emitted) beside the verify forward's hidden states there, writing
    its plane's rows of those positions and nothing else, and the next
    draft, its greedy token at the last of them, goes into the new
    state. Rejected positions are rolled back in every plane, the MTP
    plane included, as above.
    """
    import jax
    import jax.numpy as jnp

    refuse_state(model.cfg, "the speculative verify step (it rolls back "
                 "rejected K/V rows, not a state)")
    refuse_groups(model.cfg, "the speculative verify step")
    S = int(spec_tokens)
    if S < 1:
        raise ValueError(f"spec_tokens={spec_tokens}: must be >= 1")
    if mtp:
        refuse_mtp(model.cfg)
        if S != mtp_depth(model.cfg):
            raise ValueError(
                f"spec_tokens={spec_tokens}: the MTP verify step drafts "
                f"one token a module, and the model has "
                f"{mtp_depth(model.cfg)}")
    C = S + 1

    def _verify_mtp(params, pools, tables, state):
        # one draft a live lane, clipped to its budget like any other
        return _verify(params, pools, tables, state, state.draft[:, None],
                       state.live)

    def _verify(params, pools, tables, state, draft, draft_len):
        block_size = pools[0].shape[2]
        live = jnp.minimum(state.live, 1).astype(jnp.int32)
        alive = live > 0
        # a draft may never reach past the lane's budget: emitting n
        # tokens writes n-1 draft positions, so draft_len is capped at
        # remaining-1 and the chunk never writes beyond the sequence's
        # admitted total (whose blocks the scheduler guarantees)
        dl = jnp.clip(draft_len, 0, jnp.maximum(state.remaining - 1, 0))
        chunk = jnp.concatenate([state.tokens[:, None], draft], axis=1)
        width = jnp.where(alive, 1 + dl, 0).astype(jnp.int32)

        # snapshot the chunk's slots BEFORE the forward so rejected
        # writes can be rolled back afterwards. Positions past a lane's
        # table clamp inside the gather; their restore writes put back
        # the very values just read — a no-op, not corruption.
        positions = state.lengths[:, None] + jnp.arange(C)[None, :]
        blocks = jnp.take_along_axis(
            tables, jnp.minimum(positions // block_size,
                                tables.shape[1] - 1), axis=1)
        offsets = positions % block_size
        layers = jnp.arange(pools[0].shape[0])[:, None, None]
        orig = tuple(p[layers, blocks, offsets] for p in pools)
        # the forward updates the pools in place. Writing the snapshot
        # straight back changes no value and makes the pool the forward
        # sees the successor of the one just read: XLA then orders the
        # read before the in-place writes; left unordered, it copies
        # each whole pool to keep the old version readable
        pools = tuple(p.at[layers, blocks, offsets].set(o)
                      for p, o in zip(pools, orig))

        cache = PagedCache(pools, tables, state.lengths, width)
        logits, cache, *hidden, stats = _paged_apply(
            model, params, chunk, cache, **({"with_hidden": True}
                                            if mtp else {}))

        # per-position resample: position i's draw is the plain
        # decoder's emission `emitted + i` — same ops, same fold_in,
        # same logprob, so acceptance == equality with plain decode
        preds, logps = [], []
        for i in range(C):
            t_i, lp_i = sample_tokens(
                logits[:, i],
                dataclasses.replace(state.sample,
                                    emitted=state.sample.emitted + i))
            preds.append(t_i)
            logps.append(lp_i)
        pred = jnp.stack(preds, axis=1)
        logp = jnp.stack(logps, axis=1)

        # longest accepted prefix: draft[i] must equal what the plain
        # decoder produced at position i, for every earlier i too
        ar = jnp.arange(S)[None, :]
        match = (pred[:, :S] == draft) & (ar < dl[:, None])
        accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                         axis=1)
        # the plain decoder stops at its first EOS: clip the emission
        # to one past the first predicted EOS, and to the budget
        is_eos = (state.eos[:, None] >= 0) & (pred == state.eos[:, None])
        no_eos = jnp.cumprod(1 - is_eos.astype(jnp.int32), axis=1)
        lead = jnp.sum(no_eos, axis=1)          # positions before 1st EOS
        eos_limit = jnp.where(lead < C, lead + 1, C + 1)
        n_emit = jnp.minimum(accept + 1,
                             jnp.minimum(eos_limit, state.remaining))
        n_emit = jnp.where(alive, n_emit, 0).astype(jnp.int32)

        if mtp:
            # the MTP module at the committed positions: position L + j
            # reads the token it emitted, pred[:, j]
            new_draft, cache, stats = _mtp_apply(
                model, params, pred, hidden[0],
                dataclasses.replace(cache, live=n_emit),
                jnp.maximum(n_emit - 1, 0), stats)

        # roll back rejected slots: restore originals everywhere except
        # the committed prefix, whose restore writes go to block 0
        committed = jnp.arange(C)[None, :] < n_emit[:, None]
        rb = jnp.where(committed, 0, blocks)
        new_pools = tuple(p.at[layers, rb, offsets].set(o)
                          for p, o in zip(cache.pools, orig))

        retired = alive & ((lead < n_emit) | (state.remaining <= n_emit))
        last = jnp.take_along_axis(
            pred, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
        token = jnp.where(alive & (n_emit > 0), last, state.tokens)
        new_state = DecodeState(
            tokens=token,
            lengths=state.lengths + n_emit,
            live=jnp.where(retired, 0, live),
            remaining=state.remaining - n_emit,
            eos=state.eos,
            sample=dataclasses.replace(
                state.sample, emitted=state.sample.emitted + n_emit),
            draft=jnp.where(alive, new_draft, state.draft) if mtp else None)
        drafts = (jnp.where(alive, new_draft, -1),) if mtp else ()
        return (new_pools, new_state, pred, logp, n_emit, *drafts, *stats)

    program = jax.jit(_verify_mtp if mtp else _verify, donate_argnums=(1, 3))
    program.query_rows = _query_rows(model, C)
    return program


@functools.lru_cache(maxsize=8)
def build_beam_program(model, beam_k: int, decode_width: int = 2):
    """The beam-search step:
    ``(params, pools, tables, tokens (B,), lengths (B,), live (B,)) ->
    (pools, top_tok (B, beam_k), top_lp (B, beam_k))``.

    The decode program's forward — identical chunk shape, identical
    K/V write path — returning the ``beam_k`` highest-logprob
    continuations per lane instead of one sampled token, so the host
    can run hypothesis selection. ``top_lp`` is the full-distribution
    ``log_softmax`` value (the same quantity :func:`sample_tokens`
    reports), and ``lax.top_k`` breaks ties toward the lowest index
    exactly like ``argmax`` — which is why a width-1 beam is
    bit-identical to plain greedy decode, logprobs included. Beam
    state (tokens/lengths/live/tables) is host-managed: the beam loop
    is synchronous and re-forms the batch every step as beams fork and
    finish. The pools are donated."""
    import jax
    import jax.numpy as jnp

    refuse_state(model.cfg, "beam search (a fork shares and copies "
                 "blocks, not a state)")
    refuse_groups(model.cfg, "beam search")
    K = int(beam_k)
    if K < 1:
        raise ValueError(f"beam_k={beam_k}: must be >= 1")

    def _beam_step(params, pools, tables, tokens, lengths, live):
        B = tokens.shape[0]
        chunk = jnp.zeros((B, decode_width), jnp.int32)
        chunk = chunk.at[:, 0].set(tokens)
        live = jnp.minimum(live, 1).astype(jnp.int32)
        cache = PagedCache(pools, tables, lengths, live)
        logits, cache, _ = _paged_apply(
            model, params, chunk, cache,
            logits_at=jnp.zeros((B,), jnp.int32))
        top_lp, top_tok = jax.lax.top_k(
            jax.nn.log_softmax(logits, axis=-1), K)
        return cache.pools, top_tok.astype(jnp.int32), top_lp

    program = jax.jit(_beam_step, donate_argnums=(1,))
    program.query_rows = _query_rows(model, decode_width)
    return program
