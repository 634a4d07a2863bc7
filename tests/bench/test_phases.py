"""Device time by phase scope and idle time by program span, on a
synthetic trace: the parts add up, and a gap is split at span edges."""
import pytest

from bench import phases, trace

MS = 1e6   # ns

HLO = """\
ENTRY %main.1 (p.1: f32[8]) -> f32[8] {
  %while.3 = (s32[], f32[8]) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(_round)/sfprompt.phase1_local/while" stack_frame_id=2}
  %fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%f1, metadata={op_name="jit(_round)/sfprompt.phase1_local/vmap(while)/body/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(_round)/sfprompt.phase2_split/mul"}
  %copy.4 = f32[8]{0} copy(%fusion.2)
  %sort.5 = f32[8]{0} sort(%copy.4), metadata={op_name="jit(_round)/sfprompt.phase1b_prune/sort"}
  ROOT %fusion.6 = f32[8]{0} fusion(%sort.5), kind=kLoop, calls=%f6, metadata={op_name="jit(_round)/sfprompt.phase3_aggregate/add"}
}
"""


def _devices():
    return {"/device:TPU:0": [
        (0 * MS, 4 * MS, "%while.3 = (s32[], f32[8]) while(...)"),
        (1 * MS, 2 * MS, "%fusion.1 = f32[8] fusion(f32[8] %p.1)"),
        (2 * MS, 3 * MS, "copy-done.7"),  # compiler-added, in the loop
        (5 * MS, 6 * MS, "fusion.2"),
        (6 * MS, 7 * MS, "copy.4"),
        (7 * MS, 8 * MS, "sort.5"),
        (9 * MS, 11 * MS, "fusion.6"),   # runs past the window's end
    ]}


def test_hlo_scopes_reads_op_metadata():
    scopes = phases.hlo_scopes(HLO)
    assert scopes == {"while.3": "sfprompt.phase1_local",
                      "fusion.1": "sfprompt.phase1_local",
                      "fusion.2": "sfprompt.phase2_split",
                      "sort.5": "sfprompt.phase1b_prune",
                      "fusion.6": "sfprompt.phase3_aggregate"}
    assert phases.scope_of("jit(f)/sfprompt.phase2_splitx/add") == "other"


def test_phase_seconds_add_up_to_busy():
    devices = _devices()
    host = [(0, 10 * MS, trace.WINDOW_SPAN)]
    got = phases.phase_seconds(devices, 0, 10 * MS,
                               phases.hlo_scopes(HLO))
    # the loop's stretches between its body ops, and the unscoped copy
    # inside it, are its own scope's; the copy at the top level is
    # `other`; fusion.6 counts up to the window's end
    assert got == pytest.approx({
        "sfprompt.phase1_local": 0.004, "sfprompt.phase1b_prune": 0.001,
        "sfprompt.phase2_split": 0.001, "sfprompt.phase3_aggregate": 0.001,
        "other": 0.001})
    busy = trace.reduce(devices, host)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_gap_split_between_put_and_gather():
    """A gap that starts inside the benchmark's gather and ends inside
    the program's copy to the device is split at the span edges, each
    piece charged to the innermost span covering it."""
    devices = {"/device:TPU:0": [(0, 1 * MS, "fusion.1"),
                                 (7 * MS, 10 * MS, "fusion.2")]}
    host = [(0, 10 * MS, trace.WINDOW_SPAN),
            (0, 10 * MS, "bench.round"),
            (0.5 * MS, 10 * MS, "fed.round"),
            (2 * MS, 5 * MS, "fed.gather"),
            (2.5 * MS, 4.5 * MS, "bench.gather"),
            (5.5 * MS, 8 * MS, "fed.put"),
            (3 * MS, 4 * MS, "serve.prefill")]   # not a program span here
    got = phases.gap_seconds(devices, host)
    assert got == pytest.approx({
        "fed.round": 0.0015, "fed.gather": 0.001, "bench.gather": 0.002,
        "fed.put": 0.0015})
    red = trace.reduce(devices, [h for h in host
                                 if h[2].startswith("bench.")])
    assert sum(got.values()) == pytest.approx(red["window_s"]
                                              - red["busy_s"])
    # the midpoint rule charges the whole [1, 7] ms gap to the gather
    assert dict(red["breakdown"]["idle_gaps"]) == pytest.approx(
        {"bench.gather": 0.006})


def test_charge_innermost_and_uncovered():
    got = phases.charge([(0, 10)], [(2, 8, "outer"), (4, 6, "inner")])
    assert got == {None: 4, "outer": 4, "inner": 2}
    assert phases.charge([], [(0, 1, "x")]) == {}


def test_median_ms_of_span_records():
    recs = [{"kind": "span", "name": "fed.put", "dur_ns": d}
            for d in (3e6, 1e6, 2e6)]
    recs.append({"kind": "event", "name": "fed.put"})
    assert phases.median_ms(recs, "fed.put") == 2.0
    assert phases.median_ms(recs, "fed.gather") is None
