"""The generation engine: params lifecycle + the continuous batcher.

:class:`GenerationEngine` is the decode-native sibling of
:class:`~horovod_tpu.serving.engine.InferenceEngine`, glued from the
same parts:

* the shared :class:`~horovod_tpu.serving.engine.ParamsLifecycle` —
  checkpoint restore onto the serving mesh plus zero-downtime
  hot-reload (the ``serving.reload`` fault site and
  ``hvd_tpu_serving_hot_swaps_total`` apply unchanged). The scheduler
  snapshots the params reference once per device call, so a hot-swap
  lands *between* prefill/decode steps, never inside one; a sequence
  spanning a swap continues greedily under the new params (documented
  behavior — decode caches are value-compatible, not step-pinned);
* a :class:`~horovod_tpu.serving.generation.scheduler.ContinuousBatcher`
  over a paged KV cache
  (:mod:`~horovod_tpu.serving.generation.kv_cache`), sized by
  ``HVD_TPU_GEN_NUM_BLOCKS`` x ``HVD_TPU_GEN_BLOCK_SIZE``.

The model is any flax module with the paged ``apply`` contract
(``docs/serving_models.md``): ``apply(params, tokens,
cache=PagedCache, logits_at=None) -> (logits, PagedCache)``, and a
``cfg`` that **declares its cache** (``cfg.cache_spec()``, a
:class:`~horovod_tpu.models.transformer.CacheSpec`: planes, row names
and widths, dtype; pools, block bytes, the disagg wire and the programs
are driven by it) and gives ``max_seq_len`` and ``vocab_size``. A model
with routed experts also gives ``cfg.held_experts``; its programs then
return routing counts. A model whose declaration names per-sequence
``state`` gets a state slot a lane and ``HVD_TPU_GEN_STATE_SNAPSHOTS``
snapshot slots beside the block pool; it is served with the prefix cache
on like any other, and refused by what cannot carry a state: a
``spec_mode`` other than off raises here, no beam program is built and
``num_beams > 1`` is rejected at submit, and the disagg KV transfer
refuses its cache (:class:`~.kv_cache.PerSequenceStateError`). A model
whose declaration names **plane groups** (window planes beside full
ones) gets a second pool and allocator for the window group, sized from
the declaration and the engine's own knobs (``num_blocks`` stays the
size of the group that keeps every token; the window group gets, for
every lane, the blocks of a window, a prefill chunk and two more, which
no traffic can exhaust), and is refused by the same three paths
(:class:`~.kv_cache.PlaneGroupsError`).
:class:`~horovod_tpu.models.transformer.Transformer`,
:class:`~horovod_tpu.models.longcat_flash.LongcatFlash`,
:class:`~horovod_tpu.models.olmo_hybrid.OlmoHybrid` and
:class:`~horovod_tpu.models.command_a_plus.CommandAPlus` are the four.
"""

from typing import Any, List, Optional, Sequence

from ... import config as _config
from ..engine import ParamsLifecycle
from .kv_cache import (BlockAllocator, build_beam_program,
                       build_decode_program, build_prefill_program,
                       build_verify_program, make_pools, make_state_pools,
                       refuse_groups, refuse_state, state_bytes)
from .scheduler import DECODE_WIDTH, ContinuousBatcher, GenSequence
from .spec import make_proposer


class GenerationEngine:
    """Serve autoregressive generation from ``model`` with continuous
    batching, paged KV cache, and checkpoint hot-reload.

    Args:
      model: the decode-capable model (see module docstring).
      checkpoint_dir / params / sharding / step / reload_poll_seconds:
        the :class:`ParamsLifecycle` contract — exactly one of
        ``params`` and ``checkpoint_dir``.
      eos_id: default EOS token id for submitted sequences (per-request
        override wins; None runs every sequence to its ``max_tokens``).
      async_depth: decode steps the scheduler keeps in flight past the
        one being consumed (0 = synchronous; see
        ``HVD_TPU_GEN_ASYNC_DEPTH``).
      prefix_cache: automatic prefix caching — full KV blocks are
        content-indexed and shared across sequences, retired blocks
        park in a cached-free LRU pool, and admitted prompts skip
        prefill over their longest cached prefix (None reads
        ``HVD_TPU_GEN_PREFIX_CACHE``, default on; cached-prefix decode
        is bit-identical to cold decode either way).
      spec_mode: speculative decoding proposer — ``off`` | ``ngram``
        (prompt-lookup self-drafting) | ``draft`` (requires
        ``draft_model``). None reads ``HVD_TPU_GEN_SPEC_MODE``. Spec
        output is bit-identical to plain decode (greedy AND seeded
        sampling, logprobs included) — the knob only buys throughput.
      spec_tokens: static draft width of the compiled verify program
        (None reads ``HVD_TPU_GEN_SPEC_TOKENS``).
      max_beams: widest ``num_beams`` this engine accepts; the beam
        step program is compiled for this top-K. 1 disables beam
        search entirely (None reads ``HVD_TPU_GEN_BEAMS``).
      state_snapshots: snapshot slots for a model that declares
        per-sequence state (None reads
        ``HVD_TPU_GEN_STATE_SNAPSHOTS``); ignored for any other.
      draft_model / draft_params / draft_checkpoint_dir: the small
        draft transformer for ``spec_mode='draft'`` and its params
        plumbing (restored through its own :class:`ParamsLifecycle`).
      on_step: optional scheduler observability hook
        (``on_step(phase, [seq_id, ...])``).

    Knob-backed arguments (``block_size``, ``num_blocks``, ``max_seqs``,
    ``prefill_chunk``, ``queue_depth``, ``deadline_ms``,
    ``async_depth``, ``prefix_cache``) default to their registered
    generation knobs (docs/configuration.md).
    """

    def __init__(self, model, checkpoint_dir: Optional[str] = None,
                 params: Any = None, sharding=None,
                 step: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 max_seqs: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 async_depth: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 reload_poll_seconds: Optional[float] = None,
                 spec_mode: Optional[str] = None,
                 spec_tokens: Optional[int] = None,
                 max_beams: Optional[int] = None,
                 state_snapshots: Optional[int] = None,
                 draft_model=None, draft_params: Any = None,
                 draft_checkpoint_dir: Optional[str] = None,
                 on_step=None, role: Optional[str] = None):
        cfg = _config.live_config()
        block_size = int(cfg.get(_config.GEN_BLOCK_SIZE)
                         if block_size is None else block_size)
        num_blocks = int(cfg.get(_config.GEN_NUM_BLOCKS)
                         if num_blocks is None else num_blocks)
        spec_mode = str(cfg.get(_config.GEN_SPEC_MODE)
                        if spec_mode is None else spec_mode).strip().lower()
        spec_tokens = int(cfg.get(_config.GEN_SPEC_TOKENS)
                          if spec_tokens is None else spec_tokens)
        max_beams = int(cfg.get(_config.GEN_BEAMS)
                        if max_beams is None else max_beams)
        self.model = model
        self._lifecycle = ParamsLifecycle(
            checkpoint_dir=checkpoint_dir, params=params, sharding=sharding,
            step=step, reload_poll_seconds=reload_poll_seconds,
            plane="generation")
        spec_off = spec_mode in ("", "off", "0", "false", "none")
        stateful = bool(model.cfg.cache_spec().state)
        snapshots, state_slots, snapshot_slots = (), 0, 0
        if stateful:
            # a state slot a decode lane, and the snapshot pools; what
            # cannot carry a state is refused before anything is built
            if not spec_off:
                refuse_state(model.cfg, f"spec_mode={spec_mode!r}: "
                             f"speculative decoding")
            state_slots = max_seqs = int(
                cfg.get(_config.GEN_MAX_SEQS)
                if max_seqs is None else max_seqs)
            snapshot_slots = int(cfg.get(_config.GEN_STATE_SNAPSHOTS)
                                 if state_snapshots is None
                                 else state_snapshots)
            snapshots = make_state_pools(model.cfg, snapshot_slots + 1)
            max_beams = 1
        groups = model.cfg.cache_spec().groups
        window, window_span, pool_sizes = None, 0, num_blocks
        if groups:
            # one more pool and allocator, for the window group; what
            # keeps one block list a sequence is refused
            if not spec_off:
                refuse_groups(model.cfg, f"spec_mode={spec_mode!r}: "
                              f"speculative decoding")
            window, window_span = self._window_group(
                groups, block_size, prefix_cache,
                int(cfg.get(_config.GEN_MAX_SEQS)
                    if max_seqs is None else max_seqs),
                int(cfg.get(_config.GEN_PREFILL_CHUNK)
                    if prefill_chunk is None else prefill_chunk))
            pool_sizes = (num_blocks, window.num_blocks)
            max_beams = 1
        self.allocator = BlockAllocator(
            num_blocks, block_size, prefix_cache=prefix_cache,
            state_slots=state_slots, snapshot_slots=snapshot_slots,
            state_bytes=state_bytes(model.cfg), window=window,
            window_span=window_span)
        pools = make_pools(model.cfg, pool_sizes, block_size,
                           state_slots=state_slots)
        self._proposer = make_proposer(
            spec_mode, draft_model=draft_model, params=draft_params,
            checkpoint_dir=draft_checkpoint_dir) if not spec_off else None
        verify_prog = (build_verify_program(model, spec_tokens)
                       if self._proposer is not None else None)
        beam_prog = (build_beam_program(model, max_beams, DECODE_WIDTH)
                     if max_beams > 1 else None)
        self.batcher = ContinuousBatcher(
            (build_prefill_program(model),
             build_decode_program(model, DECODE_WIDTH)),
            lambda: self._lifecycle.snapshot()[0],
            pools, self.allocator,
            max_seq_len=model.cfg.max_seq_len, max_seqs=max_seqs,
            prefill_chunk=prefill_chunk, queue_depth=queue_depth,
            deadline_ms=deadline_ms, eos_id=eos_id,
            vocab_size=model.cfg.vocab_size, async_depth=async_depth,
            verify_program=verify_prog, proposer=self._proposer,
            spec_mode=spec_mode, spec_tokens=spec_tokens,
            beam_program=beam_prog, max_beams=max_beams,
            snapshots=snapshots, on_step=on_step, role=role)
        self._lifecycle.start_poller()    # last: nothing can fail past here

    @staticmethod
    def _window_group(groups, block_size, prefix_cache, max_seqs,
                      prefill_chunk):
        """The window group's allocator and its window in blocks. The
        pool holds, for every lane, the most a running sequence keeps:
        the window, a prefill chunk and two blocks of slack (the block
        being filled, and a release that lags a step in flight); cached
        blocks only ever take what lanes leave free."""
        if len(groups) != 2 or groups[0].window is not None \
                or not groups[1].window:
            raise ValueError(
                f"plane groups {tuple(g.name for g in groups)}: the "
                f"engine serves one group that keeps every token and, "
                f"after it, one window group")
        window = int(groups[1].window)
        if window % block_size:
            raise ValueError(
                f"window of {window} tokens is not whole blocks of "
                f"{block_size} (HVD_TPU_GEN_BLOCK_SIZE)")
        span = window // block_size
        lane = span + -(-prefill_chunk // block_size) + 2
        return BlockAllocator(max_seqs * lane + 1, block_size,
                              prefix_cache=prefix_cache,
                              group=groups[1].name), span

    # -- generation ----------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_tokens: int = 16,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None,
               request_id: Optional[str] = None,
               budget_ms: Optional[float] = None,
               sample_offset: int = 0,
               num_beams: Optional[int] = None) -> GenSequence:
        """Admit one request; returns the sequence handle for
        :meth:`result` / :meth:`stream`. Raises ``QueueFullError``
        (503) / ``DeadlineExceededError`` (429) / ``ValueError``
        (400) with the serving plane's admission semantics. Sampling
        runs on device: ``temperature`` (None/0 = greedy), ``top_k``,
        ``top_p``, and ``seed`` (deterministic continuations, also
        across a preemption-recompute) — see
        :meth:`ContinuousBatcher.submit`. ``request_id`` stamps the
        serving request id onto the sequence for preemption/deadline
        attribution and per-request tracing. ``budget_ms`` is the
        end-to-end latency budget (never resets, unlike
        ``deadline_ms``); ``sample_offset`` offsets the PRNG emission
        ordinal so a failover resume of ``prompt + emitted`` continues
        the original sampled stream bit-identically. ``num_beams`` > 1
        runs greedy beam search (requires an engine constructed with
        ``max_beams`` > 1); width 1 is plain decode."""
        return self.batcher.submit(prompt, max_tokens=max_tokens,
                                   eos_id=eos_id, deadline_ms=deadline_ms,
                                   temperature=temperature, top_k=top_k,
                                   top_p=top_p, seed=seed,
                                   request_id=request_id,
                                   budget_ms=budget_ms,
                                   sample_offset=sample_offset,
                                   num_beams=num_beams)

    def result(self, seq: GenSequence,
               timeout: Optional[float] = None) -> List[int]:
        return self.batcher.result(seq, timeout=timeout)

    def cancel(self, request_id: str) -> None:
        """Flag every sequence submitted under ``request_id`` for
        cancellation (``POST /v1/cancel``; hedging's loser-cancel
        path). Asynchronous and idempotent — see
        :meth:`ContinuousBatcher.cancel`."""
        self.batcher.cancel(request_id)

    def stream(self, prompt: Sequence[int], max_tokens: int = 16,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None,
               timeout: Optional[float] = None):
        """submit + yield tokens as the scheduler emits them."""
        seq = self.submit(prompt, max_tokens=max_tokens, eos_id=eos_id,
                          deadline_ms=deadline_ms, temperature=temperature,
                          top_k=top_k, top_p=top_p, seed=seed)
        return self.batcher.stream(seq, timeout=timeout)

    def generate(self, prompt: Sequence[int], max_tokens: int = 16,
                 eos_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: Optional[int] = None,
                 timeout: Optional[float] = None) -> List[int]:
        """Blocking generation: prompt tokens in, generated tokens out."""
        return self.batcher.generate(prompt, max_tokens=max_tokens,
                                     eos_id=eos_id, deadline_ms=deadline_ms,
                                     temperature=temperature, top_k=top_k,
                                     top_p=top_p, seed=seed,
                                     timeout=timeout)

    # -- lifecycle -----------------------------------------------------------

    @property
    def checkpoint_dir(self):
        return self._lifecycle.checkpoint_dir

    @property
    def step(self) -> int:
        return self._lifecycle.step

    @property
    def params(self):
        return self._lifecycle.params

    @property
    def prefix_cache(self) -> bool:
        """Whether automatic prefix caching is active on this engine."""
        return self.allocator.prefix_cache

    @property
    def role(self) -> str:
        """This engine's disagg operating mode
        (``HVD_TPU_DISAGG_ROLE``): prefill | decode | colocated."""
        return self.batcher.role

    @property
    def spec_mode(self) -> str:
        """The active speculative-decoding proposer: off|ngram|draft."""
        return self.batcher.spec_mode if self.batcher.spec else "off"

    @property
    def spec_tokens(self) -> int:
        """Static draft width of the verify program (meaningful when
        :attr:`spec_mode` != ``off``)."""
        return self.batcher.spec_tokens

    @property
    def max_beams(self) -> int:
        """Widest ``num_beams`` this engine accepts (1 = beam search
        disabled)."""
        return self.batcher.max_beams

    # -- disaggregated KV transfer surface -----------------------------------

    def kv_manifest(self, prompt: Sequence[int]) -> List[str]:
        """Content-addressed manifest for ``prompt``: chain hashes of
        its matchable full blocks (pure; identical on every replica
        sharing the block size)."""
        return self.batcher.manifest_hashes(prompt)

    def kv_probe(self, hashes: Sequence[str]) -> int:
        """Blocks of the ``hashes`` chain this engine already holds
        (longest indexed prefix; side-effect-free — the offer
        handler's zero-byte-transfer answer)."""
        return self.allocator.match_probe([str(h) for h in hashes])[0]

    def kv_export(self, hashes: Sequence[str], timeout: float = 30.0):
        """Serve ``POST /v1/kv/fetch``: read the requested blocks'
        contents off the pools (scheduler-thread control op). Returns
        ``(served_hashes, rows)``, one array for each cache pool.
        Raises :class:`~.kv_cache.PerSequenceStateError` for a model
        that declares per-sequence state, as :meth:`kv_import` does."""
        return self.batcher.execute(
            lambda: self.batcher.export_kv_blocks(hashes), timeout=timeout)

    def kv_import(self, hashes: Sequence[str],
                  payload_hashes: Sequence[str], rows,
                  timeout: float = 30.0):
        """Serve ``POST /v1/kv/offer``'s admit step: write transferred
        payloads into pool blocks and register them (remote) in the
        prefix-cache index (scheduler-thread control op). Returns
        ``(already_held, imported)``."""
        return self.batcher.execute(
            lambda: self.batcher.import_kv_blocks(
                hashes, payload_hashes, rows), timeout=timeout)

    def reload(self, step: Optional[int] = None) -> bool:
        """Force a checkpoint hot-reload now (see
        :meth:`ParamsLifecycle.reload`)."""
        return self._lifecycle.reload(step=step)

    def close(self, timeout: float = 10.0) -> None:
        """Idempotent: stop the reload poller and the scheduler thread
        (queued/running sequences fail; all KV blocks return)."""
        self._lifecycle.close(timeout=timeout)
        if self._proposer is not None and hasattr(self._proposer, "close"):
            self._proposer.close(timeout=timeout)
        self.batcher.stop(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
