"""Elastic fleet resilience: failure detection, epoch checkpoints,
stream migration, board batching, shared-domain GC.

Layered like tests/test_fleet.py: pure-python tests for the board and
the shared-tier GC, stub-worker tests for the frontend's failure
detector and recovery protocol (the migration/replay logic is exercised
here at full fidelity — greedy-decode token identity at system scale is
gated by benchmarks/fig13_elastic_fleet.py in CI), and a session-level
save_epoch/load_epoch roundtrip.
"""

import os
import time

import numpy as np
import pytest

from repro.memory.shared import SharedTier
from repro.serve.fleet.board import PrefixBoard, record_kind
from repro.serve.fleet.frontend import FleetFrontend
from repro.serve.fleet.worker import epoch_domain, load_epoch, save_epoch


# --------------------------------------------------------------------------- #
# board: record kinds + bounded batches
# --------------------------------------------------------------------------- #

def _prec(i, **extra):
    return dict({"digest": f"d{i}", "parent": "", "chunk": [i], "end": 1,
                 "nbytes": 4, "crc32": 0}, **extra)


def test_record_kind_defaults_to_prefix():
    assert record_kind(_prec(0)) == "prefix"
    assert record_kind({"kind": "epoch", "worker": "w0"}) == "epoch"


def test_board_batched_poll_exact_cursor(tmp_path):
    """max_records bounds one poll; the cursor advances exactly past
    what was returned, so nothing is skipped or replayed."""
    a, b = PrefixBoard(tmp_path), PrefixBoard(tmp_path)
    a.publish([_prec(i) for i in range(5)])
    a.publish([{"kind": "epoch", "worker": "w0", "pid": 1, "step": 4,
                "t": 0.0}])
    a.publish([_prec(i) for i in range(5, 7)])
    got = b.poll(3)
    assert [r["digest"] for r in got] == ["d0", "d1", "d2"]
    got = b.poll(3)
    assert [r.get("digest") for r in got] == ["d3", "d4", None]
    assert record_kind(got[-1]) == "epoch"
    got = b.poll(3)                      # fewer remaining than the batch
    assert [r["digest"] for r in got] == ["d5", "d6"]
    assert b.poll(3) == []
    # an unbounded poller over the same journal sees the same stream
    assert len(PrefixBoard(tmp_path).poll()) == 8


def test_board_batched_poll_with_torn_tail(tmp_path):
    a, b = PrefixBoard(tmp_path), PrefixBoard(tmp_path)
    a.publish([_prec(i) for i in range(3)])
    with open(a.path, "ab") as f:
        f.write(b'{"digest": "partial')
    assert [r["digest"] for r in b.poll(2)] == ["d0", "d1"]
    assert [r["digest"] for r in b.poll(2)] == ["d2"]
    assert b.poll(2) == []


# --------------------------------------------------------------------------- #
# shared tier: board-age GC
# --------------------------------------------------------------------------- #

def test_gc_reclaims_only_dead_and_old(tmp_path):
    tier = SharedTier(tmp_path / "dom")
    tier.put("a", b"x" * 10)
    tier.put("b", b"y" * 20)
    # our own pid is alive: everything pinned regardless of age
    res = tier.gc(ttl_s=0.0, now=time.time() + 3600)
    assert res["gc_reclaimed"] == 0 and res["gc_pinned_live"] == 2
    # publisher dead but records young: pinned by the TTL window
    res = tier.gc(ttl_s=3600.0, pid_alive=lambda p: False)
    assert res["gc_reclaimed"] == 0 and res["gc_pinned_young"] == 2
    # dead + old: reclaimed, bytes accounted, objects gone
    res = tier.gc(ttl_s=1.0, pid_alive=lambda p: False,
                  now=time.time() + 3600)
    assert res["gc_reclaimed"] == 2
    assert res["gc_reclaimed_bytes"] == 30
    with pytest.raises(KeyError):
        tier.get("a")
    assert tier.used_bytes() == 0
    assert tier.gc_stats["gc_runs"] == 3
    assert tier.gc_stats["gc_reclaimed"] == 2


def test_gc_live_publisher_pins_shared_object(tmp_path):
    """An object with one live publisher among several dead ones stays."""
    tier = SharedTier(tmp_path / "dom")
    tier.put("k", b"z" * 8)
    me = os.getpid()
    res = tier.gc(ttl_s=0.0, pid_alive=lambda p: p == me,
                  now=time.time() + 3600)
    assert res["gc_reclaimed"] == 0 and res["gc_pinned_live"] == 1
    assert tier.get("k") == b"z" * 8


def test_gc_missing_timestamp_counts_as_old(tmp_path):
    """Records from before the timestamp upgrade are infinitely old."""
    from repro.memory.shared import _DomainLock

    tier = SharedTier(tmp_path / "dom")
    tier.put("k", b"q" * 4)
    # strip the timestamp the way a pre-upgrade manifest would look
    with _DomainLock(tier._lock_path):
        m = tier._read_manifest()
        m["k"].pop("t")
        tier._write_manifest(m)
    res = tier.gc(ttl_s=10.0, pid_alive=lambda p: False)
    assert res["gc_reclaimed"] == 1


# --------------------------------------------------------------------------- #
# epoch checkpoints: save/load roundtrip through the shared tier
# --------------------------------------------------------------------------- #

class StubSched:
    def __init__(self, descs):
        self._descs = descs

    def live_descriptors(self):
        return list(self._descs)


def test_epoch_roundtrip(tmp_path):
    from repro.api.session import ResilienceSession

    descs = [
        {"sid": 0, "tokens": [1, 2, 3, 4, 5], "plen": 3,
         "emitted": [4, 5], "max_new": 4, "weight": 2},
        {"sid": 1, "tokens": [7, 8, 9], "plen": 3,
         "emitted": [], "max_new": 6, "weight": 1},
        {"sid": 2, "tokens": [6, 6], "plen": 2,
         "emitted": [], "max_new": 1, "weight": 1},
    ]
    sess = ResilienceSession.for_shared_tier(
        tmp_path, domain=epoch_domain("w7"))
    try:
        # sid 2 has no frontend rid (engine-local stream): excluded
        n = save_epoch(sess, StubSched(descs), {0: 101, 1: 102}, step=9)
        assert n == 2
    finally:
        sess.close()

    ep = load_epoch(tmp_path, "w7")
    assert set(ep) == {101, 102}
    assert ep[101]["prompt"] == [1, 2, 3]
    assert ep[101]["emitted"] == [4, 5]
    # total budget = remaining + already-emitted
    assert ep[101]["max_new_total"] == 6
    assert ep[101]["weight"] == 2 and ep[101]["step"] == 9
    assert ep[102]["prompt"] == [7, 8, 9] and ep[102]["emitted"] == []
    assert ep[102]["max_new_total"] == 6


def test_epoch_last_wins(tmp_path):
    from repro.api.session import ResilienceSession

    sess = ResilienceSession.for_shared_tier(
        tmp_path, domain=epoch_domain("w0"))
    try:
        d = {"sid": 0, "tokens": [1, 2, 3], "plen": 2, "emitted": [3],
             "max_new": 5, "weight": 1}
        save_epoch(sess, StubSched([d]), {0: 42}, step=4)
        d2 = dict(d, tokens=[1, 2, 3, 9], emitted=[3, 9], max_new=4)
        save_epoch(sess, StubSched([d2]), {0: 42}, step=8)
    finally:
        sess.close()
    ep = load_epoch(tmp_path, "w0")
    assert ep[42]["emitted"] == [3, 9] and ep[42]["step"] == 8


def test_load_epoch_missing_is_empty(tmp_path):
    assert load_epoch(tmp_path, "never-started") == {}
    assert load_epoch(tmp_path / "absent", "w0") == {}


# --------------------------------------------------------------------------- #
# failure detector + migration (stub workers — no processes, no jax)
# --------------------------------------------------------------------------- #

class DeadableWorker:
    """WorkerHandle stand-in with a controllable liveness surface: the
    test scripts heartbeats, token emissions, and process death."""

    def __init__(self):
        self.submitted = []
        self._out = []
        self.hb_age = 0.0
        self.is_alive = True

    def submit(self, rid, prompt, max_new, weight=1):
        self.submitted.append({"rid": rid, "prompt": list(prompt),
                               "max_new": int(max_new), "weight": weight})

    def emit(self, rid, tokens):
        self._out.append({"op": "tokens", "rid": rid,
                          "tokens": list(tokens)})

    def emit_done(self, rid, tokens):
        self._out.append({"op": "done", "rid": rid, "tokens": list(tokens)})

    def messages(self):
        out, self._out = self._out, []
        return out

    def heartbeat_age(self):
        return self.hb_age

    def alive(self):
        return self.is_alive

    def stats(self):
        return {}

    def stop(self):
        pass


def test_slow_but_alive_is_suspect_never_dead():
    """The detector's conjunction: heartbeat staleness alone must never
    trigger recovery — only an actually-exited process is dead."""
    w0, w1 = DeadableWorker(), DeadableWorker()
    fe = FleetFrontend([w0, w1], hb_timeout_s=1.0)
    rid = fe.submit([1, 2, 3], 5)
    fe.pump()
    assert fe.assignment(rid) == 0
    w0.emit(rid, [10, 11])
    fe.pump()
    assert fe.progress(rid) == [10, 11]
    # arbitrarily stale heartbeat, process alive: suspect, no migration
    w0.hb_age = 1e9
    for _ in range(3):
        fe.pump()
    assert fe.worker_state(0) == "suspect"
    assert fe.stats["workers_failed"] == 0
    assert fe.assignment(rid) == 0
    assert not w1.submitted
    # the worker comes back: state returns to ok, stream untouched
    w0.hb_age = 0.0
    fe.pump()
    assert fe.worker_state(0) == "ok"


def test_dead_worker_streams_migrate_with_replay():
    w0, w1 = DeadableWorker(), DeadableWorker()
    fe = FleetFrontend([w0, w1], hb_timeout_s=0.5)
    rid = fe.submit([1, 2, 3], 5)
    fe.pump()
    w0.emit(rid, [10, 11])
    fe.pump()
    # SIGKILL equivalent: stale AND exited
    w0.hb_age, w0.is_alive = 10.0, False
    fe.pump()
    assert fe.worker_state(0) == "dead"
    assert fe.stats["workers_failed"] == 1
    assert fe.stats["streams_migrated"] == 1
    assert fe.assignment(rid) == 1
    sub = w1.submitted[0]
    # the streamed prefix replays as prompt suffix; budget shrinks
    assert sub["prompt"] == [1, 2, 3, 10, 11]
    assert sub["max_new"] == 3
    # the survivor reports only its own tokens; the caller sees the
    # merged stream — identical to an uninterrupted run
    w1.emit_done(rid, [12, 13, 14])
    fe.pump()
    assert fe.result(rid) == [10, 11, 12, 13, 14]
    assert fe.stats["completed"] == 1
    assert fe.live_workers() == [1]
    assert fe.worker_stats() == [{}]     # dead worker excluded


def test_recovery_completes_budget_spent_stream():
    """A stream whose whole budget was already streamed back completes
    directly from the recovered prefix — no re-dispatch."""
    w0, w1 = DeadableWorker(), DeadableWorker()
    fe = FleetFrontend([w0, w1], hb_timeout_s=0.5)
    rid = fe.submit([4, 4], 2)
    fe.pump()
    w0.emit(rid, [5, 6])                 # full budget, "done" lost in crash
    fe.pump()
    w0.hb_age, w0.is_alive = 10.0, False
    fe.pump()
    assert fe.result(rid) == [5, 6]
    assert fe.stats["streams_completed_on_recovery"] == 1
    assert not w1.submitted


def test_recovery_prefers_longer_epoch_prefix(tmp_path):
    """The worker's last epoch may be ahead of what reached the
    frontend (the crash ate pipe messages): recovery replays the longer
    prefix — both are prefixes of the same greedy continuation."""
    from types import SimpleNamespace

    from repro.api.session import ResilienceSession

    w0, w1 = DeadableWorker(), DeadableWorker()
    w0.spec = SimpleNamespace(ckpt_every=4, shared_root=str(tmp_path),
                              name="w0")
    fe = FleetFrontend([w0, w1], hb_timeout_s=0.5)
    rid = fe.submit([1, 2], 6)
    fe.pump()
    w0.emit(rid, [30])                   # frontend saw only one token
    fe.pump()
    sess = ResilienceSession.for_shared_tier(
        tmp_path, domain=epoch_domain("w0"))
    try:
        save_epoch(sess, StubSched([
            {"sid": 0, "tokens": [1, 2, 30, 31, 32], "plen": 2,
             "emitted": [30, 31, 32], "max_new": 3, "weight": 1}]),
            {0: rid}, step=4)
    finally:
        sess.close()
    w0.hb_age, w0.is_alive = 10.0, False
    fe.pump()
    sub = w1.submitted[0]
    assert sub["prompt"] == [1, 2, 30, 31, 32] and sub["max_new"] == 3
    w1.emit_done(rid, [33, 34, 35])
    fe.pump()
    assert fe.result(rid) == [30, 31, 32, 33, 34, 35]


def test_dispatch_with_all_workers_dead_raises():
    w0 = DeadableWorker()
    fe = FleetFrontend([w0], hb_timeout_s=0.5)
    rid = fe.submit([1], 3)
    fe.pump()
    w0.emit(rid, [9])
    fe.pump()
    w0.hb_age, w0.is_alive = 10.0, False
    with pytest.raises(RuntimeError, match="no live workers"):
        fe.pump()


def test_stub_without_liveness_surface_is_trusted():
    """Handles that expose no heartbeat/liveness (legacy stubs) are
    never classified — the detector requires both signals."""
    class Plain:
        def __init__(self):
            self.submitted = []

        def submit(self, rid, prompt, max_new, weight=1):
            self.submitted.append(rid)

        def messages(self):
            return []

        def stop(self):
            pass

    fe = FleetFrontend([Plain()], hb_timeout_s=0.0)
    fe.submit([1], 1)
    fe.pump()
    assert fe.worker_state(0) == "ok"
    assert fe.stats["workers_failed"] == 0


# --------------------------------------------------------------------------- #
# unified serving API construction surface (no model build)
# --------------------------------------------------------------------------- #

def test_serve_config_worker_spec_carries_resilience_knobs(tmp_path):
    from repro.serve import ServeConfig

    cfg = ServeConfig(arch="phi3-mini-3.8b", slots=3, max_len=64,
                      page_tokens=8, ckpt_every=6, hb_interval_s=0.07,
                      adopt_batch=32, seed=5)
    spec = cfg.worker_spec(str(tmp_path), name="w9")
    assert spec.name == "w9" and spec.ckpt_every == 6
    assert spec.hb_interval_s == 0.07 and spec.adopt_batch == 32
    assert spec.slots == 3 and spec.max_len == 64
    assert spec.page_tokens == 8 and spec.seed == 5
    assert spec.shared_root == str(tmp_path)
    # the spec carries the resolved architecture, reduced unless full_size
    from repro.configs import get_config
    assert spec.arch == get_config("phi3-mini-3.8b").reduced()
    assert spec.chip is None


def test_serve_fleet_rejects_zero_workers():
    from repro.serve import Serve, ServeConfig

    with pytest.raises(ValueError):
        Serve.fleet(ServeConfig(), workers=0)


def test_serve_engine_constructor_warns_deprecated():
    import warnings

    import repro.serve.engine as eng

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = eng._WARNED_DEPRECATED
        eng._WARNED_DEPRECATED = False
        try:
            with pytest.raises(Exception):
                # cfg=None dies after the warning fires; the warning is
                # what this test pins
                eng.ServeEngine(None, None, None, batch=1, max_len=4)
        finally:
            eng._WARNED_DEPRECATED = old
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
