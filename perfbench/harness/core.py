"""What every cell shares: finding its files by name, the device check,
the compile cache, the traced window, and the result line."""

import contextlib
import copy
import importlib.util
import json
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from . import peaks, stats, tracered, traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
#: seconds of the window that a ``--trace 1`` run has the profiler on:
#: a trace of the whole window is too large to bring back and to parse
TRACE_SECONDS = 6.0
#: jax's event for every program it builds, from the cache or not
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's record."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merged(base: dict, over: Optional[dict]) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else copy.deepcopy(v)
    return out


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(spec: dict, group: str, workload: str) -> List[dict]:
    """The metrics of ``group`` that ``workload`` reports: every metric
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def reader_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    """A per-layer metric's reader: ``metrics/<name>.py``, or the file of
    the name without its last suffix (``a.b.itl`` and ``a.b.served`` are
    one quantity under two end-to-end metrics, read by ``a.b.py``)."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(bench_dir, "metrics", stem + ".py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"no reader for per-layer metric {name!r} under "
        f"{bench_dir}/metrics/")


class Context:
    """One run of one cell: what the runner needs, and what it leaves
    for the per-layer readers."""

    def __init__(self, spec, workload, seed, seconds, trace, rehearse,
                 started_at, root=ROOT, bench_dir=BENCH_DIR):
        self.spec, self.workload = spec, workload
        self.seed, self.seconds = int(seed), float(seconds)
        self.tracing, self.rehearse = bool(trace), bool(rehearse)
        self.started_at = started_at
        self.root, self.bench_dir = root, bench_dir
        entry = next(c for c in spec["configs"]
                     if c["name"] == workload["config"])
        with open(os.path.join(root, entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = traffic.load(bench_dir, workload["traffic"])
        if rehearse:
            self.config = merged(self.config, self.config.get("rehearsal"))
            self.traffic = merged(self.traffic, self.traffic.get("rehearsal"))
        self.chips = int(workload["chips"])
        # left by the runner -------------------------------------------
        self.window = None               # (t0, t1) on time.perf_counter
        self.end_to_end: Dict[str, float] = {}
        self.counters_before: Dict[str, Any] = {}
        self.counters_after: Dict[str, Any] = {}
        self.spans: Dict[str, list] = {}
        self.facts: Dict[str, Any] = {}  # what readers need, by name
        self.trace: Optional[tracered.Trace] = None
        self._trace_dir: Optional[str] = None
        self.compile_times: List[float] = []
        self.setup_marks: Dict[str, float] = {}
        self.devices = []

    # -- the cell's code, found by the names in its configuration -----------

    def load_runner(self):
        name = self.config["runner"]
        return load_module(
            os.path.join(self.bench_dir, "runners", name + ".py"),
            "perfbench_runner_" + name)

    def load_reference(self):
        name = self.config["reference"]
        return load_module(
            os.path.join(self.bench_dir, "reference", name + ".py"),
            "perfbench_reference_" + name)

    # -- device ----------------------------------------------------------

    def claim_devices(self):
        import jax

        devices = jax.devices()
        if not self.rehearse and devices[0].platform != "tpu":
            raise NoAccelerator(
                f"jax's default backend is {devices[0].platform!r} "
                f"({devices[0].device_kind}): this benchmark measures the "
                f"chip and runs nowhere else")
        if len(devices) < self.chips:
            raise NoAccelerator(
                f"cell {self.workload['name']} needs {self.chips} chips, "
                f"jax sees {len(devices)}")
        if not self.rehearse:
            peaks.peaks_for(devices[0].device_kind)
        self.devices = devices
        return devices

    @property
    def peaks(self) -> dict:
        return peaks.peaks_for(self.devices[0].device_kind)

    def device_doc(self) -> dict:
        """The device as jax reports it. ``memory_peak_bytes`` is the peak
        on the fullest chip: the allocator's ``peak_bytes_in_use``, which
        on a TPU counts the arrays a process holds and not the
        temporaries of a running program (ResNet-50 at batch 256 reads
        0.44 GB there), plus the temporaries of the cell's train step
        where the runner compiled it itself and so knows them."""
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        peak += int(self.facts.get("program_temp_bytes", 0))
        doc = {"platform": self.devices[0].platform,
               "kind": self.devices[0].device_kind,
               "count": len(self.devices), "memory_peak_bytes": peak}
        if self.trace is not None:
            doc["busy_s"] = self.trace.busy_s()
            doc["window_s"] = self.trace.window_s
        return doc

    # -- compile cache and compile counting -------------------------------

    def setup_compile_cache(self) -> str:
        """The program's own cache directory (``JAX_COMPILATION_CACHE_DIR``
        or ``<checkout>/.jax_cache``), with jax's thresholds lowered so
        that programs that compile in under a second are kept too."""
        import jax

        from horovod_tpu.compile_cache import ensure_compile_cache

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration_event)
        return ensure_compile_cache()

    def _on_duration_event(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.compile_times.append(time.perf_counter())

    def compiles_in_window(self) -> Optional[int]:
        if self.window is None:
            return None
        t0, t1 = self.window
        return sum(1 for t in self.compile_times if t0 <= t <= t1)

    # -- the traced part of the window -------------------------------------

    @contextlib.contextmanager
    def traced(self):
        """Profiler on for the body, with a ``bench.window`` annotation
        spanning it. Without ``--trace 1``, a no-op."""
        if not self.tracing:
            yield
            return
        import jax

        base = os.environ.get("TMPDIR") or tempfile.gettempdir()
        self._trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_",
                                           dir=base)
        jax.profiler.start_trace(self._trace_dir)
        try:
            with jax.profiler.TraceAnnotation(tracered.HOST_MARK + "window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def load_trace(self) -> None:
        """Read what :meth:`traced` recorded (after the window: parsing
        holds the interpreter for seconds) and delete the files."""
        if self._trace_dir is None:
            return
        try:
            events = tracered.load_xplane(self._trace_dir)
            keep = os.environ.get("PERFBENCH_KEEP_EVENTS")
            if keep:
                os.makedirs(keep, exist_ok=True)
                with open(os.path.join(
                        keep, self.workload["name"] + ".planes.json"),
                        "w") as f:
                    json.dump(tracered.describe_xplane(self._trace_dir), f,
                              indent=1)
                tracered.save_events(events, os.path.join(
                    keep, self.workload["name"] + ".events.json.gz"))
            self.trace = tracered.Trace(events)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    def annotate(self, name: str):
        """A host span in the profiler's trace (a no-op context when the
        run is not traced)."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(tracered.HOST_MARK + name)

    # -- counters ----------------------------------------------------------

    def counter_delta(self, series: str) -> Optional[float]:
        after = self.counters_after.get(series)
        if after is None:
            return None
        return after - (self.counters_before.get(series) or 0.0)

    def histogram_mean(self, series: str) -> Optional[float]:
        after = self.counters_after.get(series)
        if after is None:
            return None
        return stats.histogram_mean_delta(
            self.counters_before.get(series), after)

    def mark(self, name: str) -> None:
        """Seconds of set-up spent when ``name`` was reached."""
        self.setup_marks[name] = round(
            time.perf_counter() - self.started_at, 3)

    def info(self, **doc) -> None:
        """A line of context before the result line."""
        print(json.dumps({"info": doc}), flush=True)


def seed_key(seed: int):
    """A raw threefry key from any whole-number seed, however large."""
    import jax.numpy as jnp
    import numpy as np

    return jnp.asarray(
        np.random.SeedSequence(int(seed)).generate_state(2, np.uint32))


def result_line(ctx: Context, outcome: dict) -> dict:
    """The run's one JSON object: end-to-end metrics without the
    profiler, per-layer metrics with it."""
    name = ctx.workload["name"]
    values: Dict[str, Any] = {}
    ctx.load_trace()
    if not ctx.tracing:
        for m in metrics_of(ctx.spec, "end_to_end", name):
            if m["name"] not in ctx.end_to_end:
                raise KeyError(
                    f"cell {name} did not measure its end-to-end metric "
                    f"{m['name']}")
            values[m["name"]] = (ctx.end_to_end[m["name"]], m["unit"])
    else:
        for m in metrics_of(ctx.spec, "per_layer", name):
            reader = load_module(reader_path(m["name"], ctx.bench_dir),
                                 "perfbench_reader_"
                                 + m["name"].replace(".", "_").replace(
                                     "-", "_"))
            value = reader.read(ctx)
            if value is not None:
                values[m["name"]] = (value, m["unit"])
    doc = {"correct": bool(outcome["correct"]),
           "attempted": int(outcome["attempted"]),
           "failed": int(outcome["failed"]),
           "metrics": {k: {"value": None if ctx.rehearse else float(v),
                           "unit": u} for k, (v, u) in values.items()},
           "device": ctx.device_doc()}
    if ctx.rehearse:
        # a number from a CPU run is never written under the name of a
        # device metric: a rehearsal proves the path and reports no value
        doc["rehearsal"] = True
    if ctx.trace is not None:
        doc["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                            "idle_gaps": ctx.trace.idle_gaps(10)}
    return doc
