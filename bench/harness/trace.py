"""Reduction of a profiler trace to device time, idle time and spans.

The profiler's ``.xplane.pb`` is flattened to plain records
``{"plane", "line", "name", "start_ns", "dur_ns"}`` by :func:`load`, so
the reduction below runs the same on a trace recorded on the chip and on
the small recorded trace the tests keep.

* Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per operation and ``XLA Modules`` one per program execution.
* Host spans are the benchmark's own ``TraceAnnotation`` events, whose
  names start with ``bench.``.  The traced window is the ``bench.window``
  span.
* The device's clock and the host's differ by about a millisecond. Each
  program run on the device carries a correlation id that the host's
  ``CompleteCallbacks`` event for that run carries too, and the host sees
  a run finish only after the device ends it; the offset is taken as the
  least such difference, and device times are moved onto the host's clock.
* A device event belongs to the innermost host span open at its start.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DONE_EVENT = "CompleteCallbacks"


def load(trace_dir: str) -> List[dict]:
    """Flatten the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            keep = (device and line.name in ("XLA Ops", "XLA Modules")) or (
                plane.name == "/host:CPU")
            if not keep:
                continue
            for ev in line.events:
                host_done = not device and ev.name == DONE_EVENT
                if not device and not host_done and not ev.name.startswith(SPAN_PREFIX):
                    continue
                rec = {"plane": plane.name, "line": line.name, "name": ev.name,
                       "start_ns": int(ev.start_ns), "dur_ns": int(ev.duration_ns)}
                if host_done or line.name == "XLA Modules":
                    corr = dict(ev.stats).get("_c")
                    if corr is None:
                        continue
                    rec["corr"] = int(corr)
                out.append(rec)
    return out


def short_name(name: str) -> str:
    """An HLO instruction's name and result type (``%fusion.3 f32[8,5120]``)
    from the full text a TPU trace gives each operation."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:80]
    kind = "tuple" if rhs.startswith("(") else rhs.split("{")[0].split(" ")[0]
    return f"{lhs} {kind}"[:80]


def union_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of ``[start, end)`` intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle ``(start, end)`` stretches of [lo, hi) not covered by intervals."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        out.append((cursor, hi))
    return out


class Spans:
    """Host spans, for finding the innermost one open at a time."""

    def __init__(self, events: Sequence[dict]):
        spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                 for e in events if not DEVICE_PLANE.match(e["plane"])
                 and e["name"].startswith(SPAN_PREFIX) and e["name"] != WINDOW_SPAN]
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t: int) -> str:
        """Name of the innermost span open at ``t`` ("host" if none)."""
        best = None
        i = bisect.bisect_right(self.starts, t)
        # spans are short and few overlap; walk back over the candidates
        for s, e, name in reversed(self.spans[max(0, i - 64):i]):
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "host"

    def named(self, name: str, lo: int, hi: int) -> List[Tuple[int, int]]:
        return [(s, e) for s, e, n in self.spans if n == name and s >= lo and e <= hi]


@dataclasses.dataclass
class Reduced:
    window_ns: Tuple[int, int]
    busy_ns: Dict[int, int]  # device index -> busy union inside the window
    op_ns_by_span: Dict[str, int]  # chip 0's op time, by enclosing span
    modules: List[Tuple[str, int, int]]  # chip 0's (span, start, dur) per program run
    spans: Spans
    breakdown: dict
    clock_offset_ns: int  # added to device times to put them on the host's clock

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def busy_s(self, device: int = 0) -> float:
        return self.busy_ns.get(device, 0) / 1e9

    def idle_share(self, device: int = 0) -> float:
        return 1.0 - self.busy_ns.get(device, 0) / (self.window_ns[1] - self.window_ns[0])


def clock_offset_ns(events: Sequence[dict]) -> int:
    """Least (host saw run done - device ended run) over correlated runs;
    0 when the trace correlates none."""
    ends = {e["corr"]: e["start_ns"] + e["dur_ns"] for e in events
            if "corr" in e and DEVICE_PLANE.match(e["plane"])}
    diffs = [e["start_ns"] - ends[e["corr"]] for e in events
             if e["name"] == DONE_EVENT and e.get("corr") in ends]
    return min(diffs) if diffs else 0


def reduce(events: Sequence[dict], top: int = 10) -> Reduced:
    win = [e for e in events if e["name"] == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo = win[0]["start_ns"]
    hi = lo + win[0]["dur_ns"]
    shift = clock_offset_ns(events)
    events = [dict(e, start_ns=e["start_ns"] + shift) if DEVICE_PLANE.match(e["plane"]) else e
              for e in events]
    spans = Spans(events)
    ops: Dict[int, List[Tuple[int, int, str]]] = {}
    modules: List[Tuple[str, int, int]] = []
    for e in events:
        m = DEVICE_PLANE.match(e["plane"])
        if not m:
            continue
        dev = int(m.group(1))
        if e["line"] == "XLA Ops":
            ops.setdefault(dev, []).append((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"]))
        elif dev == 0 and lo <= e["start_ns"] < hi:
            modules.append((spans.at(e["start_ns"]), e["start_ns"], e["dur_ns"]))
    busy = {d: union_ns([(s, t) for s, t, _ in v], lo, hi) for d, v in ops.items()}
    by_span: Dict[str, int] = {}
    by_op: Dict[str, int] = {}
    chip0 = sorted(ops.get(0, []))
    for k, (s, t, name) in enumerate(chip0):
        if not lo <= s < hi:
            continue
        if k + 1 < len(chip0) and chip0[k + 1][0] < t:
            continue  # a loop or call around the operations that follow: count those
        span = spans.at(s)
        dur = min(t, hi) - s
        by_span[span] = by_span.get(span, 0) + dur
        key = f"{span}:{short_name(name)}"
        by_op[key] = by_op.get(key, 0) + dur
    idle = gaps_ns([(s, t) for s, t, _ in ops.get(0, [])], lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    breakdown = {
        "device_ops": [[k, v / 1e9] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[spans.at((s + t) // 2), (t - s) / 1e9] for s, t in idle[:top]],
    }
    return Reduced((lo, hi), busy, by_span, modules, spans, breakdown, shift)


def per_span_program_ms(r: Reduced, span: str) -> Optional[Tuple[float, int]]:
    """Total device milliseconds of program runs launched inside ``span``,
    and how many runs there were (None when there were none)."""
    runs = [d for s, _, d in r.modules if s == span]
    if not runs:
        return None
    return sum(runs) / 1e6, len(runs)
