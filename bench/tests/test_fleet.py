"""The fleet driver on the CPU at a tiny size: two worker processes
behind the program's router, an open loop with a shared system prompt,
the loss of a worker, the check against the reference, and the rate
sweep's windows."""

import time

import pytest

import tiny
import run
import sweep
from harness import correct, fleet, metrics
from harness.traffic import Arrivals, warmup_rounds


def read(name, ctx):
    return metrics.reader(tiny.BENCH / "metrics", name)(ctx)


@pytest.fixture(scope="module")
def window():
    seed = 2 ** 33 + 17
    cfg, mix = tiny.fleet_config(), tiny.fleet_mix()
    t = time.perf_counter()
    w = run.serve(cfg, mix, seed, 4.0, False)
    return seed, cfg, mix, w, w.device, t


def test_every_worker_has_exited(window):
    import multiprocessing

    _, _, _, w, device, _ = window
    assert isinstance(w, fleet.FleetWindow)
    assert device["count"] == 2 and device["platform"] == "cpu"
    assert [x["read_at_end"] for x in w.workers] == [True, True]
    assert multiprocessing.active_children() == []


def test_open_loop_sends_on_its_schedule(window):
    seed, _, mix, w, _, _ = window
    dues = [s.due - w.t0 for s in w.streams]
    # the first request at the window's start, then the seed's gaps
    arrivals, want = Arrivals(mix, seed), [0.0]
    while want[-1] + 1e-9 < 4.0:
        want.append(want[-1] + arrivals.next())
    assert dues == pytest.approx(want[:len(dues)])
    assert len(dues) >= len(want) - 2
    done = [s for s in w.streams if len(s.tokens) >= s.req.max_new]
    assert done, "nothing finished"
    for s in w.streams:
        assert s.req.prompt[:16] == w.streams[0].req.prompt[:16]
        assert all(t >= s.due for t in s.times)
        assert s.times == sorted(s.times)


def test_fleet_window_is_correct_and_reports(window):
    seed, cfg, mix, w, device, t = window
    res = run.report(tiny.fleet_cell(), cfg, mix, tiny.spec(), seed, False,
                     device, w, t)
    assert res["correct"] is True, res["checks"]
    assert "migrated_short" not in res["checks"]
    assert res["device"]["memory_peak_bytes"] >= 0
    # no worker was lost: the loss readers find nothing to read
    ctx = metrics.Context(cfg=cfg, window=w, setup_s=1.0, chips=2,
                          device=device)
    assert w.kill_t is None and not w.migrate
    for name in ("fleet.detect_ms", "fleet.resume_ms"):
        assert read(name, ctx) is None


KILL_AFTER = 40


@pytest.fixture(scope="module")
def lost():
    """w0 SIGKILLed once the window has delivered ``KILL_AFTER`` tokens.
    The frontend probes its workers' liveness at every pump
    (``hb_timeout_s`` 0), so that it finds the loss before it dispatches
    again: with the cell's 2 s, a dispatch to the dead worker inside
    that time raises out of ``pump()``, a fault of the program that this
    test of the harness does not cover."""
    seed = 2 ** 33 + 29
    cfg = tiny.fleet_config()
    cfg["serving"]["hb_timeout_s"] = 0.0
    mix = tiny.fleet_mix(kill={"worker": 0, "after_tokens": KILL_AFTER})
    t = time.perf_counter()
    w = run.serve(cfg, mix, seed, 6.0, False)
    return seed, cfg, mix, w, t


def test_the_kill_falls_at_its_token_count(lost):
    _, _, _, w, _ = lost
    assert w.lost == 0 and w.kill_t is not None
    # the loop looks at the count once a pump, a few tokens at a time
    assert KILL_AFTER <= w.kill_tokens < KILL_AFTER + 16
    assert [x["read_at_end"] for x in w.workers] == [False, True]
    failed = [fleet.merged_counter(w.stats[k], "frontend.workers_failed")
              for k in ("start", "end")]
    assert failed == [0, 1]
    assert w.recover is not None and w.recover[0] >= w.kill_t
    moved = {i for i, _, _ in w.migrate}
    assert moved and moved == {i for i, s in enumerate(w.streams)
                               if s.migrated}


def test_moved_streams_are_checked(lost, monkeypatch):
    """The check's sample takes the moved streams the limits ask for and
    holds them to the reference; a sample that lacks them is not
    correct."""
    seed, cfg, mix, w, t = lost
    cell = tiny.fleet_cell()
    done = [s for s in w.streams if len(s.tokens) >= s.req.max_new]
    n_moved = sum(s.migrated for s in done)
    # the streams w0 held when it was lost: one or two, by timing
    assert n_moved >= 1, "no moved stream finished"
    base = correct.load_limits(cell["name"])
    for want, ok in ((n_moved, True), (n_moved + 1, False)):
        lim = dict(base, migrated=want)
        monkeypatch.setattr(correct, "load_limits", lambda name, x=lim: x)
        _, picked, short = run.checked_sample(cell, w, seed)
        moved = [(s.req.prompt, s.tokens) for s in done if s.migrated]
        assert sum(p in moved for p in picked) >= min(want, n_moved)
        res = run.report(cell, cfg, mix, tiny.spec(), seed, False, w.device,
                         w, t)
        assert res["correct"] is ok, res["checks"]
        assert res["checks"]["migrated_short"]["value"] == want - min(
            want, n_moved)


def test_loss_readers_on_the_window(lost):
    _, cfg, _, w, _ = lost
    ctx = metrics.Context(cfg=cfg, window=w, setup_s=1.0, chips=2,
                          device=w.device)
    detect, resume = read("fleet.detect_ms", ctx), read("fleet.resume_ms",
                                                       ctx)
    # found at the next pump after the kill
    assert detect is not None and 0 <= detect < 1000
    assert resume is not None and resume > 0


def test_loss_readers_on_a_window_by_hand():
    """SIGKILL at 10 s, recovery from 12.0 s; stream 0 moved with 2
    tokens replayed and its survivor's first token at 12.5 s, stream 1
    moved with 1 replayed and its first at 12.3 s, stream 2 moved with
    its budget spent (no survivor token)."""
    from harness.local import Stream
    from harness.traffic import Request

    def stream(times):
        return Stream(Request(0, [1, 2], len(times)), 0, 0.0,
                      times=list(times), tokens=[7] * len(times))

    w = fleet.FleetWindow(
        t0=0.0, t1=30.0, streams=[stream([9.0, 9.5, 12.5, 12.6]),
                                  stream([9.9, 12.3]), stream([9.1, 9.2])],
        steps=[], prefills=[], compiles=0, kill_t=10.0,
        recover=(12.0, 0.004),
        migrate=[(0, 12.001, 2), (1, 12.002, 1), (2, 12.003, 2)])
    ctx = metrics.Context(cfg=tiny.fleet_config(), window=w, setup_s=1.0,
                          chips=4, device=tiny.DEVICE)
    for name, want in (("fleet.detect_ms", 2000.0),
                       ("fleet.resume_ms", 500.0)):
        assert read(name, ctx) == pytest.approx(want), name


def test_sweep_sees_the_backlog_grow_above_the_knee():
    """One fleet, two sweep windows: well below what two tiny workers
    serve, nothing is left waiting; far above it, more requests wait at
    the window's end than at its middle."""
    seed = 2 ** 33 + 41
    cfg, mix = tiny.fleet_config(), tiny.fleet_mix()
    with fleet.launched(cfg, seed) as fe:
        dev = fleet.device_of(fe)
        fleet.warm(fe, warmup_rounds(mix, seed, cfg["vocab_size"]))
        lo, hi = (sweep.window(fe, cfg, dict(mix, rate_per_s=r), seed + i,
                               3.0, None, dev)
                  for i, r in enumerate((2.0, 60.0)))
    assert lo["unfinished_end"] <= 2 and lo["finished"] >= lo["sent"] - 2
    assert hi["unfinished_end"] > hi["unfinished_mid"] > lo["unfinished_mid"]
    for res in (lo, hi):
        assert res["tokens_per_s"] > 0 and res["ttft_p95_ms"] > 0
