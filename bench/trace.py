"""From a profiler trace of the measured window to device numbers.

The JAX profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it. On a TPU each chip is a plane `/device:TPU:<n>` whose line `XLA Ops`
holds one event per operation that ran on the device (start and duration
in ns, on the same clock as the host planes). The host plane `/host:CPU`
holds, among the runtime's own events, the `TraceAnnotation` spans the
benchmark puts around its calls (names starting `bench.`); one of them,
`bench.window`, spans the traced window.

The reduction keeps to plain interval arithmetic, so that it can be
checked on a synthetic trace (tests/bench):

- busy: the union of the operation intervals of a chip, clipped to the
  window, averaged over the chips;
- time and count by op name: the summed durations, and the number, of
  the operations that share a name once the `.<n>` suffix is dropped (a
  Pallas kernel's custom call is named after the kernel: `%<kernel>.<n>
  = ... custom-call(...)`; other ops that merely take its result as an
  operand do not count);
- device ops: the operations that took most time in all (a loop or a
  branch counts through the ops of its body, not as one op);
- idle gaps: each gap between busy intervals, charged to the innermost
  `bench.` span that covers its midpoint.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

WINDOW_SPAN = "bench.window"
# ops that hold other ops (a scan's loop, a branch): their time is their
# body's, which the trace lists op by op
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench."


@contextlib.contextmanager
def capture(directory: str):
    """Profile the block into `directory` (emptied first)."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str):
    """The trace that `capture` wrote, as plain tuples: ({chip plane:
    [(start, end, op text)]}, [(start, end, host span name)])."""
    import jax
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return devices, host


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def base_name(name: str) -> str:
    """`sfprompt_paged_decode_attention.3` -> `sfprompt_paged_decode_attention`."""
    return re.sub(r"\.\d+$", "", name)


def reduce(devices: Dict[str, List[Tuple[float, float, str]]],
           host: List[Tuple[float, float, str]], *, top: int = 10) -> Dict:
    """busy_s and window_s (averaged over chips), device seconds and
    calls by op name, and the breakdown of device ops and idle gaps."""
    windows = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("the trace holds no bench.window span or no chip")
    lo, hi = windows[0][0], windows[0][1]
    spans = [(s, e, n) for s, e, n in host if n != WINDOW_SPAN]
    busy, by_name, count = [], defaultdict(float), defaultdict(int)
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        merged = merge(clip(((s, e) for s, e, _ in ops), lo, hi))
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for s, e, t in ops:
            name = op_name(t)
            if lo <= s < hi and not name.startswith(CONTAINERS):
                op_time[name] += (min(e, hi) - s) / 1e9
                by_name[base_name(name)] += (min(e, hi) - s) / 1e9
                count[base_name(name)] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gap_time[_span_at(spans, (g0 + g1) / 2)] += (g1 - g0) / 1e9
    n = len(devices)
    rank = lambda d: sorted(([k, v / n] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) / 1e9,
        "op_s": {k: v / n for k, v in by_name.items()},
        "op_n": {k: v / n for k, v in count.items()},
        "breakdown": {"device_ops": rank(op_time),
                      "idle_gaps": rank(gap_time)},
    }


def _span_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost (shortest) bench span covering time t."""
    best: Optional[Tuple[float, str]] = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "no bench span"
