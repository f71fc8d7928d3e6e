"""The trace reduction on a small hand-made trace with known numbers, and
on a slice of a trace recorded on a TPU v5e, against numbers counted here
by a different method (a 1-microsecond raster)."""

import gzip
import json
from pathlib import Path

import pytest

import tiny  # noqa: F401
from harness import tracefile

MS = 1_000_000   # ns


def hand_trace():
    """10 ms window.  Device: decode run 1-4 ms (ops 1-2, 2.5-4), prefill
    run 5-6 ms (op 5-6), decode run 8-9.5 ms (op 8-9.5) and an op that
    overlaps the window's end; the first decode run's loop op spans its
    two ops, and names carry their HLO text, as on the chip.  The step's
    two shapes are one module under two ids, and an eager program of
    another name runs at 7 ms.  Host spans: a step over 0-4.5 ms holding
    the decode call, a prefill span over 4.2-4.8 ms holding the prefill
    call, a submit span over 6.5-7.9 ms, and the last decode call."""
    return {
        "step_module": "jit__lambda",
        "devices": {"/device:TPU:0": {
            "modules": [["jit__lambda(11)", 1 * MS, 3 * MS],
                        ["jit__lambda(22)", 5 * MS, 1 * MS],
                        ["jit_scatter(33)", 7 * MS, int(0.2 * MS)],
                        ["jit__lambda(11)", 8 * MS, int(1.5 * MS)]],
            "ops": [["%while.9 = (s32[], bf16[8]) while(...)", 1 * MS,
                     3 * MS],
                    ["%fusion.1 = bf16[8] fusion(...)", 1 * MS, 1 * MS],
                    ["fusion.2", int(2.5 * MS), int(1.5 * MS)],
                    ["fusion.1", 5 * MS, 1 * MS],
                    ["scatter.4", 7 * MS, int(0.2 * MS)],
                    ["fusion.2", 8 * MS, int(1.5 * MS)],
                    ["copy.3", int(9.8 * MS), 1 * MS]]}},
        "host": [["bench.window", 0, 10 * MS],
                 ["bench.step", 0, int(4.5 * MS)],
                 ["bench.call_decode", int(0.7 * MS), int(0.1 * MS)],
                 ["bench.prefill", int(4.2 * MS), int(0.6 * MS)],
                 ["bench.call_prefill", int(4.3 * MS), int(0.1 * MS)],
                 ["bench.submit", int(6.5 * MS), int(1.4 * MS)],
                 ["bench.call_decode", int(7.95 * MS), int(0.02 * MS)]],
    }


def test_hand_trace():
    r = tracefile.reduce(hand_trace())
    # busy: 3 (the loop, 1-4) + 1 + 0.2 + 1.5 + 0.2 (clipped at the
    # window's end) = 5.9 ms
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(5.9e-3)
    assert r["programs"]["decode"] == {"calls": 2,
                                       "seconds": pytest.approx(4.5e-3)}
    assert r["programs"]["prefill"] == {"calls": 1,
                                        "seconds": pytest.approx(1e-3)}
    gaps = {(name, round(s * 1e3, 6)) for name, s in r["idle_gaps"]}
    # idle: 0-1 (step), 4-5 (middle 4.5: prefill), 6-7 (middle 6.5:
    # submit), 7.2-8 (middle 7.6: submit), 9.5-9.8 (no span); 2-2.5
    # lies inside the loop
    assert gaps == {("bench.step", 1.0), ("bench.prefill", 1.0),
                    ("bench.submit", 1.0), ("bench.submit", 0.8),
                    ("bench.outside_spans", 0.3)}
    # the loop holds two ops and is not counted beside them
    ops = dict((k, round(v * 1e3, 6)) for k, v in r["device_ops"])
    assert ops == {"decode/fusion.2": 3.0, "decode/fusion.1": 1.0,
                   "prefill/fusion.1": 1.0, "jit_scatter(33)/scatter.4": 0.2,
                   "other/copy.3": 0.2}


@pytest.mark.parametrize("drop", ["bench.call_prefill", "jit__lambda(22)"])
def test_runs_and_calls_that_disagree_fail(drop):
    """A call with no run, or a run with no call, fails the reduction
    rather than naming the wrong runs."""
    tr = hand_trace()
    tr["host"] = [h for h in tr["host"] if h[0] != drop]
    dev = tr["devices"]["/device:TPU:0"]
    dev["modules"] = [m for m in dev["modules"] if m[0] != drop]
    with pytest.raises(ValueError, match="runs of jit__lambda"):
        tracefile.reduce(tr)


def test_one_id_as_two_shapes_fails():
    tr = hand_trace()
    tr["host"][-1][0] = "bench.call_prefill"
    with pytest.raises(ValueError, match="both shapes"):
        tracefile.reduce(tr)


def raster_busy(tr):
    """Busy microseconds by marking every microsecond an op covers."""
    lo = next(h for h in tr["host"] if h[0] == tracefile.WINDOW)
    start, end = lo[1] // 1000, (lo[1] + lo[2]) // 1000
    busy = 0
    for dev in tr["devices"].values():
        marks = bytearray(end - start)
        for _, s, d in dev["ops"]:
            a, b = max(s // 1000, start), min((s + d) // 1000, end)
            for i in range(a, b):
                marks[i - start] = 1
        busy += sum(marks)
    return busy / len(tr["devices"]) * 1e-6, (end - start) * 1e-6


RECORDED = Path(__file__).parent / "data" / "v5e_codegen_slice.json.gz"


def test_recorded_slice():
    tr = json.load(gzip.open(RECORDED, "rt"))
    want = json.loads((RECORDED.parent / "v5e_codegen_slice.expected.json")
                      .read_text())
    r = tracefile.reduce(tr)
    busy, window = raster_busy(tr)
    # the raster rounds each op to whole microseconds
    assert r["window_s"] == pytest.approx(window, abs=2e-6)
    assert r["busy_s"] == pytest.approx(busy, rel=0.02)
    # the runs of each shape against the harness's calls, counted here
    # on the host side
    lo = next(h for h in tr["host"] if h[0] == tracefile.WINDOW)
    for key, call in tracefile.CALLS.items():
        calls = [h for h in tr["host"]
                 if h[0] == call and lo[1] <= h[1] < lo[1] + lo[2]]
        assert r["programs"][key]["calls"] == len(calls) == want[key]["calls"]
        assert r["programs"][key]["seconds"] == pytest.approx(
            want[key]["seconds"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    # chunks of 8 prompt tokens through the 16-layer stage at B=1, and
    # one decode step over 8 lanes, as the chip ran them
    p, d = r["programs"]["prefill"], r["programs"]["decode"]
    assert 0.05 < p["seconds"] / p["calls"] < 0.2
    assert 0.01 < d["seconds"] / d["calls"] < 0.03
