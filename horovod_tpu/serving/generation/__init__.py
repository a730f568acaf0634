"""Continuous-batching generation: the decode-native serving plane.

The PR 5 serving stack batches *requests* into fixed-shape forwards —
right for classification/embedding, wrong for autoregressive decode,
where sequences finish at different lengths and memory wants to track
live tokens. This package is the decode-native plane layered on the
same admission machinery:

* :mod:`.kv_cache` — paged KV cache: fixed-size block pools
  (``HVD_TPU_GEN_BLOCK_SIZE`` x ``HVD_TPU_GEN_NUM_BLOCKS``), a strict
  refcounting block allocator with automatic prefix caching
  (``HVD_TPU_GEN_PREFIX_CACHE``: content-indexed full blocks, a
  cached-free LRU pool, shared prefixes across sequences), and the
  jitted prefill/decode programs — both **sample on device**
  (greedy/temperature/top-k/top-p, seeded per request) and return
  ``(B,)`` token ids + logprobs, never logits;
* :mod:`.scheduler` — :class:`ContinuousBatcher`: iteration-level
  scheduling (admit / one prefill chunk / one decode step, every step),
  immediate retirement on EOS or ``max_tokens``, preempt-and-requeue on
  block exhaustion, per-token deadlines; decode state lives on device
  (re-uploaded only on batch membership changes) and
  ``HVD_TPU_GEN_ASYNC_DEPTH=1`` overlaps host scheduling with the
  in-flight device step;
* :mod:`.engine` — :class:`GenerationEngine`: the scheduler glued to
  the shared checkpoint restore + hot-reload lifecycle
  (:class:`~horovod_tpu.serving.engine.ParamsLifecycle`);
* :mod:`.spec` — speculative decoding proposers
  (``HVD_TPU_GEN_SPEC_MODE``): n-gram self-drafting, a small draft
  model, or the model's own multi-token prediction module drafting on
  the device (``mtp``), verified k-at-a-time by
  :func:`build_verify_program` with output bit-identical to plain
  decode; beam search
  (``num_beams`` at submit, capped by ``HVD_TPU_GEN_BEAMS``) rides the
  same paged cache via :func:`build_beam_program` with
  copy-on-extend block forking.

Quick start::

    from horovod_tpu.models import Transformer, TransformerConfig
    import horovod_tpu.serving as serving

    engine = serving.GenerationEngine(
        Transformer(cfg), checkpoint_dir="/ckpts/run1", eos_id=2)
    with serving.InferenceServer(engine=None, gen_engine=engine,
                                 port=8500):
        ...   # POST /v1/generate {"prompt": [...], "max_tokens": 32}
    for tok in engine.stream([1, 5, 9], max_tokens=64):
        ...   # in-process streaming

See docs/inference.md for architecture, knobs, metrics, and drills.
"""

from .engine import GenerationEngine                        # noqa: F401
from .kv_cache import (BlockAllocator, BlocksExhaustedError,  # noqa: F401
                       DecodeState, PerSequenceStateError, PlaneGroupsError,
                       SampleParams,
                       block_bytes,
                       build_beam_program, build_decode_program,
                       build_prefill_program, build_program,
                       build_verify_program, chain_hash, make_pools,
                       sample_tokens)
from .scheduler import ContinuousBatcher, GenSequence       # noqa: F401
from .spec import (DraftModelProposer, NGramProposer,       # noqa: F401
                   Proposer, make_proposer)
