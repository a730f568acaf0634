"""The measured window of a training cell.

Whole steps finished in the window, times items a step, over the seconds
to the last step's ``block_until_ready``, per chip. One step is kept in
flight behind the one being waited for, as a training loop that logs its
loss does; nothing else runs on the host. A traced run measures
``core.TRACE_SECONDS`` with the profiler on for all of it.
"""

import math
import time

from . import core


def measure(ctx, step, state, batch, items_per_step: int):
    """``step(*state, *batch) -> (*state, loss)``. Returns
    ``(state, losses)`` and leaves the window and the end-to-end numbers
    in ``ctx``."""
    import jax

    seconds = min(ctx.seconds, core.TRACE_SECONDS) if ctx.tracing \
        else ctx.seconds
    losses = []
    pending = []

    def wait():
        with ctx.annotate("train.wait"):
            losses.append(float(jax.block_until_ready(pending.pop(0))))
        return time.perf_counter()

    with ctx.traced():
        t0 = t_last = time.perf_counter()
        while time.perf_counter() < t0 + seconds:
            with ctx.annotate("train.dispatch"):
                *state, loss = step(*state, *batch)
            pending.append(loss)
            if len(pending) > 1:
                t_last = wait()
        while pending:
            t_last = wait()
    ctx.window = (t0, t_last)
    if losses:
        ctx.end_to_end["train_items_per_s_per_chip"] = \
            len(losses) * items_per_step / (t_last - t0) / ctx.chips
    ctx.end_to_end["setup_s"] = t0 - ctx.started_at
    return state, losses


def note_program_memory(ctx, compiled) -> None:
    """Remember the temporaries of the compiled step, a chip's share:
    they are on the device while it runs, and the allocator's peak does
    not count them (``core.Context.device_doc``)."""
    analysis = compiled.memory_analysis()
    ctx.facts["program_temp_bytes"] = int(
        getattr(analysis, "temp_size_in_bytes", 0) or 0)


def outcome(ctx, first: float, ref_loss: float, losses, tolerance: float,
            **context) -> dict:
    """``correct`` for a training cell: losses finite, the last below the
    first step's on the fixed batch, the first step's within
    ``tolerance`` of the reference's, nothing built inside the window."""
    finite = all(math.isfinite(x) for x in [first, *losses])
    fell = bool(losses) and losses[-1] < first
    close = abs(first - ref_loss) <= tolerance
    ctx.info(check="first step's loss against the float32 reference",
             first_loss=first, reference_loss=ref_loss, tolerance=tolerance,
             last_loss=losses[-1] if losses else None, steps=len(losses),
             window_s=ctx.window[1] - ctx.window[0],
             compiles_in_window=ctx.compiles_in_window(),
             program_temp_bytes=ctx.facts.get("program_temp_bytes"),
             **context)
    return {"correct": finite and fell and close
            and ctx.compiles_in_window() == 0,
            "attempted": len(losses),
            "failed": sum(1 for x in losses if not math.isfinite(x))}
