"""Device time by protocol phase, and idle time by program span, from a
profiler trace of federated rounds run with a live `repro.obs` tracer.

`bench/trace.py` reduces the benchmark's own traced window: busy time,
time by op name, and idle gaps charged to the `bench.` span at their
midpoint. Two things more can be read once the program's tracer is live
and its round program names its phases:

- The round program wraps each protocol phase in `jax.named_scope`
  (`SCOPES`); every compiled instruction carries its scope in the
  `op_name` of its metadata, under the instruction name the device trace
  uses. `hlo_scopes` reads that map from the compiled program's text.
  `phase_seconds` charges each instant the chip is busy to the scope of
  the innermost op running then that carries one, and to `other` where
  none does: the parts add up to the busy time. Copies and async copies
  the compiler adds carry no metadata; inside a phase's loop they count
  to that phase, through the loop op that holds them.
- A live tracer's spans (`fed.*`, `sfprompt.*`) are profiler
  annotations on the host plane, beside the benchmark's `bench.*` ones.
  `gap_seconds` splits each idle gap at the edges of the spans it
  crosses and charges each piece to the innermost span covering it, and
  to `no span` where none does: the parts add up to the idle time.

Plain interval arithmetic on the tuples `bench/trace.py::load` returns,
so that it can be checked on a synthetic trace (tests/bench).
"""
from __future__ import annotations

import re
import statistics
from collections import defaultdict
from typing import Dict, Hashable, Iterable, List, Optional, Tuple

from bench.trace import WINDOW_SPAN, clip, merge, op_name

SCOPES = ("sfprompt.phase1_local", "sfprompt.phase1b_prune",
          "sfprompt.phase2_split", "sfprompt.phase3_aggregate")
OTHER = "other"
SPAN_PREFIXES = ("bench.", "fed.", "sfprompt.")
NO_SPAN = "no span"

Op = Tuple[float, float, str]            # (start_ns, end_ns, op text)

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bmetadata=\{[^}]*'
    r'\bop_name="([^"]*)"')


def scope_of(path: str) -> str:
    """The phase scope named in an op's `op_name` path, or `other`."""
    parts = path.split("/")
    for scope in SCOPES:
        if scope in parts:
            return scope
    return OTHER


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: phase scope} over every instruction of a
    compiled program's text (`jit(f).lower(...).compile().as_text()`)
    whose metadata names one; the others are `other` by omission."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and scope_of(m.group(2)) != OTHER:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def charge(cover: Iterable[Tuple[float, float]],
           labelled: Iterable[Tuple[float, float, Hashable]]
           ) -> Dict[Optional[Hashable], float]:
    """Nanoseconds of the union of `cover` by the label of the innermost
    (shortest) labelled interval covering each instant; None for instants
    no labelled interval covers. The values add up to the union's
    length."""
    cover = merge(cover)
    labelled = [(s, e, lab) for s, e, lab in labelled if e > s]
    edges = sorted({x for s, e in cover for x in (s, e)}
                   | {x for s, e, _ in labelled for x in (s, e)})
    starts = defaultdict(list)
    ends = defaultdict(list)
    for i, (s, e, _) in enumerate(labelled):
        starts[s].append(i)
        ends[e].append(i)
    out: Dict[Optional[Hashable], float] = defaultdict(float)
    active: set = set()
    k = 0                                    # first cover piece not past t0
    for t0, t1 in zip(edges, edges[1:]):
        active.difference_update(ends.get(t0, ()))
        active.update(starts.get(t0, ()))
        while k < len(cover) and cover[k][1] <= t0:
            k += 1
        # the cover's own edges are among `edges`, so [t0, t1] lies
        # wholly inside cover[k] or wholly before it
        if k == len(cover) or cover[k][0] >= t1:
            continue
        inner = min(active, default=None,
                    key=lambda i: labelled[i][1] - labelled[i][0])
        out[None if inner is None else labelled[inner][2]] += t1 - t0
    return dict(out)


def phase_seconds(devices: Dict[str, List[Op]], lo: float, hi: float,
                  scopes: Dict[str, str]) -> Dict[str, float]:
    """Busy seconds of the window [lo, hi] by phase scope, averaged over
    the chips; `scopes` maps instruction names to scopes (`hlo_scopes`).
    Every scope and `other` has a key; the values add up to busy_s."""
    total = dict.fromkeys(SCOPES + (OTHER,), 0.0)
    for ops in devices.values():
        ops = [(max(s, lo), min(e, hi), op_name(t))
               for s, e, t in ops if e > lo and s < hi]
        scoped = [(s, e, scopes[n]) for s, e, n in ops if n in scopes]
        for lab, ns in charge(((s, e) for s, e, _ in ops), scoped).items():
            total[OTHER if lab is None else lab] += ns / 1e9 / len(devices)
    return total


def gap_seconds(devices: Dict[str, List[Op]],
                host: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle seconds of the `bench.window` span by the innermost program
    span (`SPAN_PREFIXES`) covering each piece of each gap, averaged over
    the chips; `no span` where none covers it. The values add up to
    window_s - busy_s."""
    lo, hi = next((s, e) for s, e, n in host if n == WINDOW_SPAN)
    spans = [(s, e, n) for s, e, n in host
             if n != WINDOW_SPAN and n.startswith(SPAN_PREFIXES)]
    total: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        busy = merge(clip(((s, e) for s, e, _ in ops), lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        for lab, ns in charge(gaps, spans).items():
            total[NO_SPAN if lab is None else lab] += ns / 1e9 / len(devices)
    return dict(total)


def median_ms(records: Iterable[dict], name: str) -> Optional[float]:
    """Median duration in ms of the tracer's span records named `name`,
    None where there are none."""
    durs = [r["dur_ns"] / 1e6 for r in records
            if r.get("kind") == "span" and r["name"] == name]
    return statistics.median(durs) if durs else None
