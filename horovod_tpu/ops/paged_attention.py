"""Paged attention for a few query columns, as a Pallas TPU kernel.

The serving decode, verify and beam programs attend ``C`` new columns
of every lane (``C`` is 2, or ``spec + 1``) to what the lane already
holds in the paged KV pool. The plain formulation
(``Attention.__call__``'s gather path in :mod:`..models.transformer`)
gathers every slot of every lane's block table into a contiguous
``(B, T, H, D)`` view and runs softmax attention over all ``T`` of them,
so a step costs the table, whatever the lanes hold. This kernel reads
the pool where it lies:

* the K and V pools ``(planes, num_blocks, block_size, row)`` stay in
  HBM, whole: no plane is sliced out, no table is gathered. The block
  tables, each lane's number of rows to read and the plane's index ride
  in SMEM (scalar prefetch), and the plane's index is a run-time value,
  so every layer of a model calls the same kernel;
* a lane walks ``ceil(rows / GROUP_TOKENS)`` groups of its table and no
  more, a group being ``GROUP_TOKENS`` tokens' worth of blocks copied to
  VMEM by one ``make_async_copy`` a block, double-buffered: while a
  group is scored the next one (the lane's own, or the first of the
  next live lane) is in flight. A lane with ``rows == 0`` (dead: its
  ``lengths`` is stale) starts no copy and writes zeros;
* online softmax over the groups with float32 running maximum, sum and
  accumulator; scores come off the MXU in float32, probabilities are
  cast to the pool's dtype for ``P x V``, which accumulates in float32;
* a token's row is read **as it lies**: ``row`` values, of which the
  first ``H * D`` are the heads' and the rest padding. The ``C * H``
  query vectors are laid out block-diagonally over the row
  (``q_rows[c * H + h, h * D:(h + 1) * D] = q[c, h]``), so the scores of
  a group are one ``(C * H, row) x (row, tokens)`` matmul and the
  output one ``(C * H, tokens) x (tokens, row)`` matmul whose diagonal
  blocks are the heads' outputs; a 0/1 selection matmul folds them into
  ``(C, row)``, the ``(B, C, H * D)`` order the output projection
  wants. No ``(..., H, D)``-shaped copy of K or V exists, and nothing
  costs ``max_blocks``. It spends ``H`` times the needed FLOPs on
  zeros, which a step bound by bytes does not feel.

**Grouped queries.** Where a token's row holds fewer key-value heads
than ``q`` has heads (``kv_heads``: grouped-query attention, 128 query
heads over 8 key-value heads of 128), the block-diagonal row would be
``C * H`` query rows wide for ``kv_heads * D`` columns: 256 FLOP a byte
at those sizes, past what the chip can feed. The ``C * H / kv_heads``
query vectors that share a key-value head are then scored against that
head's ``D`` columns alone (``(C * rep, D) x (D, tokens)``, a lane-aligned
slice of the copied group: ``D`` is a multiple of 128), a head after a
head, 32 FLOP a byte. Which layout runs follows from the shapes
(``kv_heads < H``), not from an option; a model whose rows hold one head
a query head keeps the block-diagonal layout.

**One pool for keys and values.** Latent attention in its absorbed
form is one key-value head whose key is a token's whole row and whose
value is the same row (:mod:`..models.longcat_flash`): ``v_pool=None``
says so, and a group is then copied once and serves both matmuls, since
a second copy of the same rows would double the bytes of a step that is
bound by them. Its queries are folded through a weight, so their width
is the row's and not what the scores are scaled by: ``scale`` gives
that (``None``: ``D ** -0.5``).

**Windows.** With ``window`` a lane's walk starts at the group that
holds position ``lengths - window + 1`` (a per-lane first group in SMEM
beside the row count) and a slot is masked by its position
(``last - window < t <= last``), so a table entry before the window is
never read and may be the null block: what a window group's released
blocks leave behind.

Nothing here knows 25 x 64: the shapes come from the pools' ``(block_size,
row)`` and from ``q``. What selects this kernel over the gather path is
:func:`kernel_applies`, a rule over shapes and the backend; there is no
option. ``interpret=True`` runs the same body through the Pallas
interpreter (what the CPU tests pass); without it a backend that is not
a TPU raises, and a TPU never interprets.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, use_pallas_default

#: tokens a grid step scores at once: a group of ``GROUP_TOKENS //
#: block_size`` blocks (8 blocks of 16). One block a step would be
#: thousands of steps a layer; a larger group rounds a lane's rows up
#: further.
GROUP_TOKENS = 128
#: the most query rows (``C * H``, padded to whole tiles) one pass takes:
#: the decode and beam programs bring 2 x heads, a verify step
#: ``(spec + 1) x heads``. A prefill chunk (64 x heads) is another
#: regime and keeps the gather path.
MAX_QUERY_ROWS = 128
_LANES = 128
_QUERY_TILE = 16        # sublanes of a packed bfloat16 tile


def _sublanes(dtype) -> int:
    """Rows of one ``(sublanes, 128)`` tile of ``dtype``."""
    return 32 // jnp.dtype(dtype).itemsize


def shapes_fit(query_rows: int, block_size: int, row: int, dtype) -> bool:
    """Whether the kernel can take these shapes: the pool's
    ``(block_size, row)`` is whole tiles of its dtype (so a block is
    copied and scored without a relayout), whole blocks make up a group,
    and the query rows one pass brings fit it: a chunk's ``C * H``, or,
    grouped, the ``C * H / kv_heads`` that share a key-value head."""
    return (block_size % _sublanes(dtype) == 0
            and GROUP_TOKENS % block_size == 0
            and row % _LANES == 0
            and 0 < query_rows <= MAX_QUERY_ROWS)


def kernel_applies(query_rows: int, block_size: int, row: int,
                   dtype) -> bool:
    """The path rule: the paged forward calls the kernel exactly when
    the default backend is a TPU (the repo's rule for every Pallas
    kernel, :func:`~.flash_attention.use_pallas_default`) and
    :func:`shapes_fit`. Everything else takes the gather path: a
    prefill chunk, a CPU run, a toy float32 pool of 4-token blocks."""
    return use_pallas_default() and shapes_fit(
        query_rows, block_size, row, dtype)


def blocks_read(lengths, chunk: int, block_size: int,
                max_blocks: int, window=None) -> int:
    """Blocks the kernel copies in one call for live lanes holding
    ``lengths`` tokens before a chunk of ``chunk`` columns (a dead lane
    reads none and is not listed): each lane's ``length + chunk`` rows,
    clipped to its table, rounded up to whole groups; with a ``window``
    from the group that holds the first key the chunk's first column
    may read (``length - window + 1``) and not from 0. Host arithmetic
    for the scheduler's counter, the same walk as the kernel's."""
    group = GROUP_TOKENS // block_size
    top = max_blocks * block_size
    return sum((-(-min(int(n) + chunk, top) // GROUP_TOKENS)
                - first_group(int(n), window)) * group for n in lengths)


def first_group(length, window):
    """The first group of ``GROUP_TOKENS`` rows a lane's walk reads: the
    one that holds position ``length - window + 1``, the oldest key the
    chunk's first column (at position ``length``) may read; 0 with no
    window. Python ints or arrays."""
    if not window:
        return length * 0
    over = length - window + 1
    return (over + abs(over)) // 2 // GROUP_TOKENS      # max(over, 0)


def _kernel(layer_ref, rows_ref, first_ref, lengths_ref, next_ref,
            tables_ref, q_ref, *refs, block_size, max_blocks, columns,
            heads, kv_heads, window, sm_scale, one_pool):
    """One lane a grid step. Scalar prefetch: ``layer_ref`` (1,) the
    plane; ``rows_ref`` (B,) rows to read, 0 for a dead lane;
    ``first_ref`` (B,) the group its walk starts at
    (:func:`first_group`); ``lengths_ref`` (B,); ``next_ref`` (B + 1,):
    entry 0 the first live lane, entry ``b + 1`` the next live lane
    after ``b`` (``B`` for none); ``tables_ref`` (B * max_blocks,).

    Two query layouts, by ``kv_heads`` (module docstring). **One head a
    key-value head** (``kv_heads == heads``): ``q_ref`` (1, Cp, row) the
    lane's ``columns`` query columns, a column's heads side by side as
    a token's row has them; ``diag_ref`` (R, row) and ``sel_ref``
    (Cp, R) the 0/1 masks that spread them block-diagonally over
    ``R >= columns * heads`` query rows and fold the output's diagonal
    blocks back into ``o_ref`` (1, Cp, row). **Grouped**: ``q_ref``
    (1, kv_heads, Rg, D), for each key-value head the ``columns x
    heads / kv_heads`` query vectors that share it (row ``c * rep +
    j``), scored against that head's ``D`` columns of a token's row;
    ``o_ref`` the same shape. Scratch: two group buffers each for K and
    V, their DMA semaphores, the float32 accumulator, and which buffer
    holds the group the next live lane starts from. With ``one_pool``
    there is no V pool and no V buffer: the K pool's rows are the values
    too, copied once."""
    grouped = kv_heads != heads
    refs = iter(refs)
    diag_ref, sel_ref = (None, None) if grouped else (next(refs), next(refs))
    k_hbm = next(refs)
    v_hbm = None if one_pool else next(refs)
    o_ref, k_buf = next(refs), next(refs)
    v_buf = None if one_pool else next(refs)
    sems, acc_ref, slot_ref = refs
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    group = GROUP_TOKENS // block_size
    layer = layer_ref[0]
    rows = rows_ref[b]
    first = first_ref[b]
    groups = (rows + (GROUP_TOKENS - 1)) // GROUP_TOKENS - first

    def copies(lane, g, slot, lookup=True):
        """The 2 x group (one pool: group) DMA descriptors of a lane's
        group ``g`` into buffer ``slot``. Waiting needs only the shapes,
        not the source, so ``lookup=False`` skips the table reads.
        Entries past the table are clipped to its last: their slots are
        masked."""
        out = []
        for j in range(group):
            blk = 0
            if lookup:
                at = jnp.minimum(g * group + j, max_blocks - 1)
                blk = tables_ref[lane * max_blocks + at]
            dst = pl.ds(j * block_size, block_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, blk], k_buf.at[slot, dst], sems.at[0, slot]))
            if not one_pool:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[layer, blk], v_buf.at[slot, dst],
                    sems.at[1, slot]))
        return out

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        lane = next_ref[0]

        @pl.when(lane < lanes)
        def _():
            for c in copies(lane, first_ref[lane], 0):
                c.start()

    @pl.when(rows == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    def visible(g, last):
        """Which of group ``g``'s slots a query row whose column sits at
        position ``last`` may read: ``t <= last``, and inside the window
        ``t > last - window``."""
        t = g * GROUP_TOKENS + jax.lax.broadcasted_iota(
            jnp.int32, last.shape, 1)
        valid = t <= last
        if window:
            valid = jnp.logical_and(valid, t > last - window)
        return valid

    def softmax_step(s, valid, m, l):
        """One group of the running softmax: ``(p, corr, m_new,
        l_new)``."""
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        return p, corr, m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True)

    def walk(score, carry):
        """The lane's groups, double-buffered; ``score(k, v, g, carry)``
        folds one group in."""
        slot0 = slot_ref[0]
        after = jnp.minimum(next_ref[b + 1], lanes - 1)
        has_after = next_ref[b + 1] < lanes

        def body(i, carry):
            g = first + i
            slot = (slot0 + i) % 2
            more = i + 1 < groups

            # the next group to score: this lane's, or the first of the
            # next live lane
            @pl.when(jnp.logical_or(more, has_after))
            def _():
                lane = jnp.where(more, b, after)
                for c in copies(lane, jnp.where(more, g + 1,
                                                first_ref[after]), 1 - slot):
                    c.start()

            for c in copies(b, g, slot, lookup=False):
                c.wait()
            k = k_buf[slot]
            return score(k, k if one_pool else v_buf[slot], g, carry)

        carry = jax.lax.fori_loop(0, groups, body, carry)
        slot_ref[0] = (slot0 + groups) % 2
        return carry

    if grouped:
        @pl.when(rows > 0)
        def _live_grouped():
            rep = heads // kv_heads
            Rg, D = q_ref.shape[2], q_ref.shape[3]
            # query row r = c * rep + j is column c's vector of the j-th
            # head of its group, and sees the slots up to lengths + c
            r = jax.lax.broadcasted_iota(jnp.int32, (Rg, GROUP_TOKENS), 0)
            last = lengths_ref[b] + jnp.minimum(r // rep, columns - 1)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def score(k, v, g, carry):
                valid = visible(g, last)
                out = []
                for h in range(kv_heads):
                    m, l = carry[h]
                    cols = slice(h * D, (h + 1) * D)
                    s = jax.lax.dot_general(
                        q_ref[0, h], k[:, cols], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
                    p, corr, m, l = softmax_step(s, valid, m, l)
                    pv = jax.lax.dot_general(
                        p.astype(v.dtype), v[:, cols],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)     # (Rg, D)
                    acc_ref[h] = acc_ref[h] * corr + pv
                    out.append((m, l))
                return tuple(out)

            m0 = jnp.full((Rg, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((Rg, 1), jnp.float32)
            carry = walk(score, ((m0, l0),) * kv_heads)
            for h in range(kv_heads):
                o_ref[0, h] = (acc_ref[h] / jnp.maximum(
                    carry[h][1], 1e-30)).astype(o_ref.dtype)
        return

    @pl.when(rows > 0)
    def _live():
        R, row = diag_ref.shape
        # query row r = c * heads + h is column c's vector, kept only
        # where head h's values lie in a token's row, and sees the slots
        # t <= lengths + c
        diag = diag_ref[...]
        columns_in = q_ref[0].astype(jnp.float32)       # (Cp, row)
        r_row = jax.lax.broadcasted_iota(jnp.int32, (R, row), 0)
        r = jax.lax.broadcasted_iota(jnp.int32, (R, GROUP_TOKENS), 0)
        q = jnp.zeros((R, row), jnp.float32)
        column = jnp.zeros_like(r)
        for c in range(columns):
            q = jnp.where(r_row >= c * heads, columns_in[c:c + 1, :], q)
            if c:
                column = column + (r >= c * heads).astype(jnp.int32)
        q = (q * diag.astype(jnp.float32)).astype(diag.dtype)
        last = lengths_ref[b] + column
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def score(k, v, g, carry):                      # (tokens, row)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            p, corr, m, l = softmax_step(s, visible(g, last), *carry)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (R, row)
            acc_ref[...] = acc_ref[...] * corr + pv
            return m, l

        m0 = jnp.full((R, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((R, 1), jnp.float32)
        _, l = walk(score, (m0, l0))

        # row c * heads + h holds head h's output in its own diagonal
        # block; everything off the diagonal is another head's values
        # under this head's probabilities. Keep the diagonal, and add a
        # column's rows together: exactly one term of each sum is not 0.
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out = (out * diag).astype(o_ref.dtype)
        o_ref[0] = jax.lax.dot_general(
            sel_ref[...], out, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _fold_masks(C, H, D, R, Cp, row, dtype):
    """``diag`` (R, row): 1 where row ``c * H + h`` meets head ``h``'s
    ``D`` values; ``sel`` (Cp, R): 1 where row ``r`` belongs to column
    ``c``."""
    r = np.arange(R)[:, None]
    j = np.arange(row)[None, :]
    diag = (r < C * H) & (j < H * D) & (j // D == r % H)
    c = np.arange(Cp)[:, None]
    sel = (np.arange(R)[None, :] < C * H) & (np.arange(R)[None, :] // H == c)
    return jnp.asarray(diag, dtype), jnp.asarray(sel, dtype)


@functools.partial(
    jax.jit, static_argnames=("kv_heads", "window", "scale", "interpret"))
def paged_attention(q, k_pool, v_pool, layer, block_tables, lengths, live,
                    *, kv_heads=None, window=None, scale=None,
                    interpret=False):
    """Attention of a chunk's ``q`` ``(B, C, H, D)`` over the paged
    cache: plane ``layer`` of ``k_pool`` / ``v_pool`` ``(planes,
    num_blocks, block_size, row)`` through ``block_tables`` ``(B,
    max_blocks)``. Lane ``b`` holds ``lengths[b]`` tokens before the
    chunk (whose own K and V the caller has already written), column
    ``c`` attends to slots ``t <= lengths[b] + c`` (with ``window``:
    and ``t > lengths[b] + c - window``; the walk then starts at the
    group of the first such slot, and a table entry before it may be
    anything, the null block too), and a lane with ``live[b] == 0`` is
    dead: nothing of its table is read and its output is zeros.
    ``kv_heads`` (None: ``H``) is how many key-value heads a token's
    row holds; with fewer than ``H`` the grouped layout runs.
    ``v_pool=None``: a token's row in ``k_pool`` is its value too, and a
    group is copied once. ``scale`` multiplies the float32 scores
    (None: ``D ** -0.5``). Returns ``(B, C, H, D)`` in the pools' dtype.

    The shapes have to satisfy :func:`shapes_fit` (asked about the
    query rows one pass brings: ``C * H``, or grouped ``C * H /
    kv_heads``); the caller's rule is :func:`kernel_applies`."""
    B, C, H, D = q.shape
    _, _, block_size, row = k_pool.shape
    G = H if kv_heads is None else int(kv_heads)
    one_pool = v_pool is None
    if not one_pool and (v_pool.shape != k_pool.shape
                         or v_pool.dtype != k_pool.dtype):
        raise ValueError(
            f"paged_attention: K pool {k_pool.shape} {k_pool.dtype} and V "
            f"pool {v_pool.shape} {v_pool.dtype} differ")
    grouped = G != H
    if H % G or not shapes_fit(C * H // G, block_size, row, k_pool.dtype) \
            or G * D > row or (grouped and D % _LANES):
        raise ValueError(
            f"paged_attention: {C} x {H} query rows ({G} key-value heads "
            f"of {D}) over blocks of {block_size} x {row} {k_pool.dtype} "
            f"do not fit the kernel (see shapes_fit)")
    max_blocks = block_tables.shape[1]
    dtype = k_pool.dtype

    lengths = lengths.astype(jnp.int32)
    rows = jnp.where(live > 0,
                     jnp.minimum(lengths + C, max_blocks * block_size), 0)
    first = first_group(lengths, window).astype(jnp.int32)
    # entry 0: the first live lane; entry b + 1: the next one after b
    lane_or_end = jnp.where(rows > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.concatenate([
        jax.lax.cummin(lane_or_end, reverse=True),
        jnp.full((1,), B, jnp.int32)])

    kernel = functools.partial(
        _kernel, block_size=block_size, max_blocks=max_blocks, columns=C,
        heads=H, kv_heads=G, window=window, one_pool=one_pool,
        sm_scale=1.0 / float(np.sqrt(D)) if scale is None else float(scale))
    whole = lambda b, *_: (0, 0)                      # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if grouped:
        rep = H // G
        Rg = -(-C * rep // _QUERY_TILE) * _QUERY_TILE
        # (B, C, G, rep, D) -> (B, G, C * rep, D): a key-value head's
        # query vectors together, column-major
        operands = (jnp.pad(
            q.astype(dtype).reshape(B, C, G, rep, D).transpose(
                0, 2, 1, 3, 4).reshape(B, G, C * rep, D),
            ((0, 0), (0, 0), (0, Rg - C * rep), (0, 0))),)
        lane_block = pl.BlockSpec((1, G, Rg, D), lambda b, *_: (b, 0, 0, 0))
        in_specs = [lane_block]
        out_shape = (B, G, Rg, D)
        acc_shape = (G, Rg, D)
    else:
        R = -(-C * H // _QUERY_TILE) * _QUERY_TILE
        Cp = -(-C // _QUERY_TILE) * _QUERY_TILE
        operands = (jnp.pad(q.astype(dtype).reshape(B, C, H * D),
                            ((0, 0), (0, Cp - C), (0, row - H * D))),
                    *_fold_masks(C, H, D, R, Cp, row, dtype))
        lane_block = pl.BlockSpec((1, Cp, row), lambda b, *_: (b, 0, 0))
        in_specs = [lane_block, pl.BlockSpec((R, row), whole),
                    pl.BlockSpec((Cp, R), whole)]
        out_shape = (B, Cp, row)
        acc_shape = (R, row)
    pools = (k_pool,) if one_pool else (k_pool, v_pool)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B,),
            in_specs=in_specs + [hbm] * len(pools),
            out_specs=lane_block,
            scratch_shapes=[
                # a double-buffered group a pool
                *[pltpu.VMEM((2, GROUP_TOKENS, row), dtype)] * len(pools),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM(acc_shape, jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        # a lane hands the next its first group already in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, first, lengths, nxt,
      block_tables.astype(jnp.int32).reshape(-1),
      *operands, *pools)
    if grouped:
        return out[:, :, :C * rep].reshape(B, G, C, rep, D).transpose(
            0, 2, 1, 3, 4).reshape(B, C, H, D)
    return out[:, :C, :H * D].reshape(B, C, H, D)
