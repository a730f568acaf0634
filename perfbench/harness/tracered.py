"""Reduction of a profiler trace to numbers.

The profiler's ``.xplane.pb`` is read once into neutral events
``{"plane", "line", "name", "start_ns", "dur_ns"}``; everything else here
is arithmetic on such lists, so the tests run it on a small recorded
trace (``tests/perfbench_suite/data``) without a device.

On a TPU the device planes are ``/device:TPU:<n>``. Their ``XLA Ops``
line holds one event per executed operation and their ``XLA Modules``
line one event per executed program (``jit_<function>(<fingerprint>)``).
Host threads are lines of ``/host:CPU``; ``jax.profiler.TraceAnnotation``
spans appear there under their own names, on the same clock.
"""

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's own host annotations all start so
HOST_MARK = "bench."
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv)")

Interval = Tuple[int, int]


def load_xplane(trace_dir: str) -> List[dict]:
    """Neutral events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_MARK):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start_ns": int(ev.start_ns),
                               "dur_ns": int(ev.duration_ns)})
    return events


def describe_xplane(trace_dir: str, samples: int = 6) -> dict:
    """Every plane and line of the newest trace with its event count and
    a few event names: what to look at before trusting the reducer on a
    new device or jax."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = {}
    if not paths:
        return out
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            names, count = [], 0
            for ev in line.events:
                count += 1
                if len(names) < samples:
                    names.append([ev.name[:200], int(ev.duration_ns)])
            out[f"{plane.name} | {line.name}"] = {"events": count,
                                                  "first": names}
    return out


def save_events(events: List[dict], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> List[dict]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def op_name(raw: str) -> str:
    """A stable, short name for a device operation: the HLO instruction
    name without its numeric suffix, and its result shape where the
    trace gives one, in the characters a metric name may have."""
    name = raw.strip().lstrip("%")
    shape = ""
    m = re.match(r"^(\S+)\s*=\s*(\S+)", name)
    if m:
        name, shape = m.group(1), m.group(2)
    name = re.sub(r"[.\d]+$", "", name) or name
    text = name + ("_" + shape if shape else "")
    text = re.sub(r"\{[^}]*\}", "", text)
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", text)[:64]


class Trace:
    """The reduced view of one traced window."""

    def __init__(self, events: List[dict],
                 window: Optional[Interval] = None):
        self.events = events
        self._lines: Dict[Tuple[str, str], List[dict]] = {}
        for e in events:
            self._lines.setdefault((e["plane"], e["line"]), []).append(e)
        self.planes = sorted({e["plane"] for e in events
                              if DEVICE_PLANE.match(e["plane"])})
        self.host_marks = sorted(
            (e for e in events if not DEVICE_PLANE.match(e["plane"])
             and e["name"].startswith(HOST_MARK)),
            key=lambda e: e["start_ns"])
        if window is None:
            marks = [e for e in self.host_marks
                     if e["name"] == HOST_MARK + "window"]
            if marks:
                window = (marks[0]["start_ns"],
                          marks[0]["start_ns"] + marks[0]["dur_ns"])
            else:
                dev = [e for e in events if e["plane"] in self.planes]
                window = (min((e["start_ns"] for e in dev), default=0),
                          max((e["start_ns"] + e["dur_ns"] for e in dev),
                              default=0))
        self.window = window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _line(self, plane: str, line: str) -> List[dict]:
        return self._lines.get((plane, line), [])

    def _busy(self, plane: str) -> List[Interval]:
        return clip(merge((e["start_ns"], e["start_ns"] + e["dur_ns"])
                          for e in self._line(plane, OPS_LINE)),
                    *self.window)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.planes:
            return 0.0
        return sum(total(self._busy(p)) for p in self.planes) \
            / len(self.planes) / 1e9

    def idle_share(self) -> Optional[float]:
        if not self.planes or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def program_ms(self, pattern: str) -> Optional[float]:
        """Mean device duration of the executed programs whose module
        name matches ``pattern``, over every chip."""
        rx = re.compile(pattern)
        durs = [e["dur_ns"] for p in self.planes
                for e in self._line(p, MODULES_LINE) if rx.search(e["name"])]
        if not durs:
            return None
        return sum(durs) / len(durs) / 1e6

    def program_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        if not self.planes:
            return 0
        return sum(1 for e in self._line(self.planes[0], MODULES_LINE)
                   if rx.search(e["name"]))

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations with most device time (seconds, mean over
        the chips), under :func:`op_name`."""
        if not self.planes:
            return []
        sums: Dict[str, float] = {}
        for p in self.planes:
            for e in self._line(p, OPS_LINE):
                key = op_name(e["name"])
                sums[key] = sums.get(key, 0.0) + e["dur_ns"]
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(self.planes) / 1e9] for k, v in ranked]

    def exposed_collective_s(self) -> Optional[float]:
        """Seconds in which a collective operation ran on a chip and no
        other operation did, mean over the chips."""
        if not self.planes:
            return None
        acc = 0
        for p in self.planes:
            coll, comp = [], []
            for e in self._line(p, OPS_LINE):
                iv = (e["start_ns"], e["start_ns"] + e["dur_ns"])
                (coll if COLLECTIVE.match(e["name"].lstrip("%"))
                 else comp).append(iv)
            acc += total(clip(subtract(merge(coll), merge(comp)),
                              *self.window))
        return acc / len(self.planes) / 1e9

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time of the first chip inside the window, summed by what
        the host was doing: each gap between operations goes to the
        benchmark annotation that overlaps it most (``bench.window``
        aside), or to ``unannotated``."""
        if not self.planes:
            return []
        gaps = subtract([self.window], self._busy(self.planes[0]))
        marks = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                 for e in self.host_marks
                 if e["name"] != HOST_MARK + "window"]
        sums: Dict[str, float] = {}
        i = 0
        for g0, g1 in gaps:
            while i < len(marks) and marks[i][1] <= g0:
                i += 1
            best, best_overlap = "unannotated", 0
            k = i
            while k < len(marks) and marks[k][0] < g1:
                overlap = min(g1, marks[k][1]) - max(g0, marks[k][0])
                if overlap > best_overlap:
                    best, best_overlap = marks[k][2], overlap
                k += 1
            sums[best] = sums.get(best, 0.0) + (g1 - g0)
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[re.sub(r"[^A-Za-z0-9_.\-]+", "_", k), v / 1e9]
                for k, v in ranked]
