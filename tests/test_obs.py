"""Observability layer: quantile sketches, registry, tracer, flight
recorder.

Layered like the rest of the suite: pure-python unit tests for the
sketch math (bias bounds vs numpy, exact mergeability), the registry
snapshot/merge protocol, the StatsView legacy shim (including the real
TierStack/KVPager wiring), the tracer's record/export surface, and the
flight recorder's append-only crash semantics through a real
SharedTier; one slow end-to-end test SIGKILLs a real worker mid-decode
and reconstructs its last-seconds timeline from the shared domain.
"""

import glob
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.memory.shared import SharedTier
from repro.obs.metrics import (
    QuantileSketch,
    Registry,
    StatsView,
    merge_snapshots,
    quantile,
)
from repro.obs.recorder import FlightRecorder, flight_key, read_flight
from repro.obs.trace import Tracer, default_tracer, set_default_tracer


# --------------------------------------------------------------------------- #
# quantile sketch: bias bound vs numpy, exact merge
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("q", [0.0, 0.01, 0.25, 0.5, 0.9, 0.99, 1.0])
def test_sketch_quantile_within_alpha_of_numpy(q):
    """The DDSketch contract: the estimate is within relative error
    ``alpha`` of an actual observed value at that rank."""
    rng = np.random.default_rng(0)
    # latency-shaped: lognormal spanning ~4 orders of magnitude
    values = rng.lognormal(mean=-7.0, sigma=2.0, size=4000)
    alpha = 0.01
    sk = QuantileSketch(alpha=alpha)
    for v in values:
        sk.observe(float(v))
    est = sk.quantile(q)
    s = np.sort(values)
    rank = q * (len(s) - 1)
    lo, hi = s[int(np.floor(rank))], s[int(np.ceil(rank))]
    assert lo * (1 - alpha) - 1e-12 <= est <= hi * (1 + alpha) + 1e-12


def test_quantile_helper_matches_numpy_within_alpha():
    rng = np.random.default_rng(1)
    values = rng.uniform(0.5, 100.0, size=2000).tolist()
    for q in (0.5, 0.95, 0.99):
        est = quantile(values, q)
        exact = float(np.quantile(values, q))
        assert abs(est - exact) <= 0.02 * exact
    assert quantile([], 0.99) == 0.0
    assert quantile([3.0], 0.5) == pytest.approx(3.0, rel=0.01)


def test_sketch_handles_negatives_and_zeros():
    sk = QuantileSketch()
    for v in [-4.0, -2.0, 0.0, 0.0, 1.0, 3.0]:
        sk.observe(v)
    assert sk.quantile(0.0) == pytest.approx(-4.0, rel=0.02)
    assert sk.quantile(1.0) == pytest.approx(3.0, rel=0.02)
    assert -2.1 <= sk.quantile(0.25) <= 0.0
    assert sk.count == 6
    assert sk.mean == pytest.approx(-2.0 / 6.0)


def test_sketch_merge_is_exactly_sketch_of_whole():
    """merge(a, b) must equal the sketch built over the concatenated
    stream — bucket-for-bucket, so every quantile answer is identical.
    This is what makes fleet-merged percentiles principled (vs averaging
    per-worker p99s, which has no such guarantee)."""
    rng = np.random.default_rng(2)
    xs = rng.lognormal(size=500)
    ys = rng.lognormal(size=700)
    a, b, whole = QuantileSketch(), QuantileSketch(), QuantileSketch()
    for v in xs:
        a.observe(float(v))
        whole.observe(float(v))
    for v in ys:
        b.observe(float(v))
        whole.observe(float(v))
    a.merge(b)
    assert a.count == whole.count
    assert a.pos == whole.pos
    da, dw = a.to_dict(), whole.to_dict()
    # summation order differs in the last float bits; buckets are exact
    assert da.pop("sum") == pytest.approx(dw.pop("sum"))
    assert da == dw
    for q in (0.01, 0.5, 0.99):
        assert a.quantile(q) == whole.quantile(q)


def test_sketch_dict_roundtrip_and_merge_guard():
    sk = QuantileSketch()
    for v in (0.001, 0.002, 0.5, -1.0, 0.0):
        sk.observe(v)
    d = sk.to_dict()
    assert d["kind"] == "qsketch" and d["count"] == 5
    back = QuantileSketch.from_dict(d)
    assert back.to_dict() == d
    assert back.quantile(0.99) == sk.quantile(0.99)
    # JSON-able end to end (it rides pipes and BENCH artifacts)
    assert QuantileSketch.from_dict(json.loads(json.dumps(d))).count == 5
    with pytest.raises(ValueError):
        sk.merge(QuantileSketch(alpha=0.05))
    with pytest.raises(ValueError):
        QuantileSketch.from_dict({"kind": "nope"})


# --------------------------------------------------------------------------- #
# registry: snapshot shape, merge semantics
# --------------------------------------------------------------------------- #

def test_registry_get_or_create_and_snapshot_nesting():
    reg = Registry()
    c = reg.counter("tier.hits_fast")
    c.inc()
    c.inc(2)
    assert reg.counter("tier.hits_fast") is c
    reg.gauge("worker.cpu_s").set(1.5)
    reg.histogram("frontend.admission_latency_s", tenant="quiet").observe(0.01)
    reg.histogram("frontend.admission_latency_s", tenant="noisy").observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["tier"]["hits_fast"] == 3
    assert snap["gauges"]["worker"]["cpu_s"] == 1.5
    hs = snap["histograms"]["frontend"]["admission_latency_s"]
    assert set(hs) == {"tenant=quiet", "tenant=noisy"}
    assert hs["tenant=quiet"]["kind"] == "qsketch"
    assert hs["tenant=quiet"]["count"] == 1
    # snapshots must survive the pipe protocol
    json.dumps(snap)


def test_merge_snapshots_sums_counters_and_merges_sketches():
    a, b = Registry(), Registry()
    a.counter("sched.steps").inc(10)
    b.counter("sched.steps").inc(5)
    b.counter("sched.parks").inc(1)
    for v in (0.001, 0.002):
        a.histogram("frontend.lat", tenant="t").observe(v)
    for v in (0.4, 0.5):
        b.histogram("frontend.lat", tenant="t").observe(v)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["counters"]["sched"]["steps"] == 15
    assert merged["counters"]["sched"]["parks"] == 1
    sk = merged["histograms"]["frontend"]["lat"]["tenant=t"]
    assert sk["count"] == 4
    # the merged view sees the union — its upper quantiles sit in b's
    # range, far above anything a observed
    back = QuantileSketch.from_dict(sk)
    assert back.quantile(1.0) == pytest.approx(0.5, rel=0.02)
    assert back.quantile(0.99) >= 0.4 * (1 - 0.011)
    assert merge_snapshots([]) == {}


# --------------------------------------------------------------------------- #
# StatsView: every legacy stats idiom, backed by registry counters
# --------------------------------------------------------------------------- #

def test_statsview_keeps_legacy_dict_idioms():
    reg = Registry()
    stats = StatsView(reg, "sched", {"steps": 0, "parks": 0})
    stats["steps"] += 3                      # in-place bump
    stats["parks"] = 2                       # assignment
    stats.setdefault("spills", 0)            # lazy key
    stats.update({"resumes": 1})             # bulk
    assert stats["steps"] == 3 and len(stats) == 4
    assert dict(stats) == {"steps": 3, "parks": 2, "spills": 0,
                           "resumes": 1}
    assert stats() == dict(stats)            # TierStack's callable form
    # the same numbers are registry counters, fleet-mergeable
    snap = reg.snapshot()
    assert snap["counters"]["sched"] == dict(stats)
    with pytest.raises(KeyError):
        stats["absent"]
    del stats["spills"]
    assert "spills" not in reg.snapshot()["counters"]["sched"]
    assert int(stats["steps"]) == 3          # integer-valued stays int-y


def test_kvpager_and_tierstack_share_one_registry():
    """The real wiring: pager counters and tier counters land in one
    registry, so one snapshot covers the whole KV path and every
    pre-obs stats key still resolves."""
    from repro.serve.kvpage import KVPager

    pager = KVPager.for_capacity(fast_bytes=1 << 20, paged=True,
                                 page_bytes=4096)
    try:
        assert pager.registry is pager.stack.registry
        legacy = pager.stack.stats()         # the pre-obs callable form
        assert "hits_fast" in legacy or "hits_hbm" in legacy
        snap = pager.registry.snapshot()
        assert set(legacy) <= set(snap["counters"]["tier"])
        assert "kv_pages_put" in snap["counters"]["kv"]
    finally:
        pager.close()


def test_frontend_stats_and_admission_latency_from_registry():
    from repro.serve.fleet.frontend import FleetFrontend

    class Plain:
        def __init__(self):
            self.submitted = []

        def submit(self, rid, prompt, max_new, weight=1):
            self.submitted.append(rid)

        def messages(self):
            return []

        def stop(self):
            pass

    fe = FleetFrontend([Plain()])
    rid = fe.submit([1, 2, 3], 4, tenant="quiet")
    fe.pump()
    assert fe.stats["submitted"] == 1 and fe.stats["dispatched"] == 1
    snap = fe.registry.snapshot()
    assert snap["counters"]["frontend"]["dispatched"] == 1
    h = snap["histograms"]["frontend"]["admission_latency_s"]
    assert h["tenant=quiet"]["count"] == 1
    assert fe.admission_latency_p99("quiet") >= 0.0
    assert fe.admission_latency_p99("never-dispatched") == 0.0
    assert rid in fe._requests


def test_fleet_stats_merges_worker_snapshots():
    from repro.serve.fleet.frontend import FleetFrontend

    def worker_snap(steps, lat):
        reg = Registry()
        reg.counter("sched.steps").inc(steps)
        reg.histogram("frontend.lat").observe(lat)
        return reg.snapshot()

    class SnapWorker:
        def __init__(self, name, snap):
            from types import SimpleNamespace
            self.spec = SimpleNamespace(name=name)
            self._snap = snap

        def submit(self, *a, **k):
            pass

        def messages(self):
            return []

        def stats(self):
            return {"registry": self._snap}

        def stop(self):
            pass

    fe = FleetFrontend([SnapWorker("w0", worker_snap(7, 0.001)),
                        SnapWorker("w1", worker_snap(5, 0.9))])
    obs = fe.fleet_stats()
    assert set(obs["workers"]) == {"w0", "w1"}
    assert obs["merged"]["counters"]["sched"]["steps"] == 12
    sk = obs["merged"]["histograms"]["frontend"]["lat"]
    assert sk["count"] == 2
    # frontend's own counters ride the same merge
    assert obs["merged"]["counters"]["frontend"]["submitted"] == 0


# --------------------------------------------------------------------------- #
# tracer: spans, events, ring bound, export, disabled no-op
# --------------------------------------------------------------------------- #

def test_tracer_span_event_records():
    tr = Tracer(process="t0")
    with tr.span("prefill", tid=3, tokens=16):
        pass
    sp = tr.begin("fetch", tid=1)
    tr.end(sp, bytes_moved=512)
    tr.event("finish", tid=3, emitted=4)
    recs = tr.records()
    assert [r["name"] for r in recs] == ["prefill", "fetch", "finish"]
    prefill, fetch, finish = recs
    assert prefill["ph"] == "X" and prefill["dur"] >= 0.0
    assert prefill["args"] == {"tokens": 16} and prefill["tid"] == 3
    # end() args merge into begin() args
    assert fetch["args"] == {"bytes_moved": 512}
    assert finish["ph"] == "i"
    assert tr.records("finish") == [finish]
    assert len(tr) == 3
    tr.clear()
    assert tr.records() == []


def test_tracer_disabled_is_noop_and_none_safe():
    tr = Tracer(enabled=False)
    with tr.span("prefill", tid=0):
        pass
    sp = tr.begin("step")
    assert sp is None
    tr.end(sp)                               # None handle accepted
    tr.end(None, extra=1)
    tr.event("finish")
    assert len(tr) == 0


def test_tracer_ring_bounded_drop_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.event(f"e{i}")
    assert [r["name"] for r in tr.records()] == ["e6", "e7", "e8", "e9"]


def test_tracer_sink_receives_every_completed_record():
    rec = FlightRecorder("w0")
    tr = Tracer(sink=rec)
    with tr.span("step"):
        pass
    tr.event("finish")
    assert rec.pending() == 2


def test_chrome_trace_export(tmp_path):
    tr = Tracer(process="w0")
    with tr.span("prefill", tid=2, tokens=8):
        pass
    tr.event("finish", tid=2)
    doc = tr.chrome_trace()
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "w0"
    assert evs[0]["ph"] == "X" and evs[0]["dur"] >= 0.0
    assert evs[0]["ts"] == pytest.approx(tr.records()[0]["ts"] * 1e6)
    assert evs[1]["ph"] == "i"
    # foreign records (a flight-recorder read-back) group by proc tag
    foreign = [{"name": "step", "ph": "X", "ts": 1.0, "dur": 0.1,
                "tid": 0, "proc": "wA"},
               {"name": "step", "ph": "X", "ts": 1.1, "dur": 0.1,
                "tid": 0, "proc": "wB"}]
    doc2 = tr.chrome_trace(foreign)
    pids = {e["pid"] for e in doc2["traceEvents"] if e["ph"] != "M"}
    assert len(pids) == 2
    out = tmp_path / "trace.json"
    tr.export(out)
    assert json.loads(out.read_text())["traceEvents"]


def _profiled_serve_events(tmp_path, record):
    """``record()`` under a CPU profiler session; the ``serve.*`` events
    of the trace's host planes as (name, start_ns, end_ns)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        record()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert paths, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_tracer_spans_land_on_the_profiler_host_plane(tmp_path):
    tr = Tracer()

    def record():
        step = tr.begin("step")          # opened and closed in two calls
        with tr.span("wait"):
            time.sleep(0.002)
        tr.event("finish")               # instants get no annotation
        tr.end(step, active=1)

    evs = {name: (s, e) for name, s, e in _profiled_serve_events(tmp_path,
                                                                  record)}
    assert set(evs) == {"serve.step", "serve.wait"}
    (s0, e0), (s1, e1) = evs["serve.step"], evs["serve.wait"]
    assert s0 <= s1 < e1 <= e0
    assert e1 - s1 >= 2_000_000          # the sleep, in ns
    # the ring is as it was: names, args, perf_counter durations
    step, wait = tr.records("step")[0], tr.records("wait")[0]
    assert step["args"] == {"active": 1} and "args" not in wait
    assert step["ts"] <= wait["ts"] and wait["dur"] >= 0.002


def test_disabled_tracer_emits_no_profiler_events(tmp_path):
    off, on = Tracer(enabled=False), Tracer()

    def record():
        with off.span("step"):
            off.end(off.begin("wait"))
        with on.span("control"):
            pass

    evs = _profiled_serve_events(tmp_path, record)
    assert [name for name, _, _ in evs] == ["serve.control"]


def test_default_tracer_swap():
    prev = set_default_tracer(Tracer(process="test"))
    try:
        assert default_tracer().process == "test"
    finally:
        set_default_tracer(prev)


# --------------------------------------------------------------------------- #
# flight recorder: bounded buffer, append-only flush, torn-tail read
# --------------------------------------------------------------------------- #

def test_recorder_bounded_pending_drops_oldest():
    rec = FlightRecorder("w0", capacity=3)
    for i in range(5):
        rec.record({"name": f"e{i}", "ph": "i", "ts": float(i)})
    assert rec.pending() == 3 and rec.dropped == 2


def test_recorder_flush_and_read_roundtrip(tmp_path):
    tier = SharedTier(tmp_path / "dom")
    rec = FlightRecorder("w3")
    rec.record({"name": "step", "ph": "X", "ts": 1.0, "dur": 0.1, "tid": 0})
    rec.record({"name": "finish", "ph": "i", "ts": 1.2, "tid": 4})
    assert rec.flush(tier) == 2
    assert rec.pending() == 0 and rec.flushed == 2
    assert rec.flush(tier) == 0              # nothing pending: no write
    rec.record({"name": "park", "ph": "i", "ts": 1.3, "tid": 4})
    rec.flush(tier)                          # second flush appends
    records, torn = read_flight(tier, "w3")
    assert torn == 0
    assert [r["name"] for r in records] == ["step", "finish", "park"]
    assert all(r["proc"] == "w3" for r in records)
    # last=N tails the timeline
    tail, _ = read_flight(tier, "w3", last=2)
    assert [r["name"] for r in tail] == ["finish", "park"]
    # a worker that never flushed reads as empty, not an error
    assert read_flight(tier, "never") == ([], 0)


def test_read_flight_tolerates_torn_tail(tmp_path):
    """A SIGKILL mid-append tears at most the final record; every line
    before it is intact because the journal is append-only."""
    tier = SharedTier(tmp_path / "dom")
    rec = FlightRecorder("w9")
    for i in range(3):
        rec.record({"name": f"e{i}", "ph": "i", "ts": float(i)})
    rec.flush(tier)
    # the kill: a half-written final record
    tier.append(flight_key("w9"), b'{"name":"e3","ph":"X","ts":3')
    records, torn = read_flight(tier, "w9")
    assert torn == 1
    assert [r["name"] for r in records] == ["e0", "e1", "e2"]


def test_recorder_failed_flush_keeps_pending():
    class Refusing:
        def append(self, key, data):
            raise OSError("shared domain unreachable")

    rec = FlightRecorder("w0")
    rec.record({"name": "step", "ph": "i", "ts": 0.0})
    with pytest.raises(OSError):
        rec.flush(Refusing())
    assert rec.pending() == 1                # buffer intact for retry


# --------------------------------------------------------------------------- #
# check_regression: metric paths resolve through sketch leaves
# --------------------------------------------------------------------------- #

def _load_check_regression():
    import importlib.util

    path = Path(__file__).resolve().parent.parent / "benchmarks" \
        / "check_regression.py"
    spec = importlib.util.spec_from_file_location("_check_regression", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_regression_resolves_sketch_stats():
    cr = _load_check_regression()
    sk = QuantileSketch()
    for v in (0.001, 0.002, 0.003, 0.8):
        sk.observe(v)
    doc = {"registry": {"merged": {"frontend": {
        "admission_latency_s": {"tenant=quiet": sk.to_dict()}}}}}
    base = "registry.merged.frontend.admission_latency_s.tenant=quiet"
    assert cr._get(doc, base + ".p99") == pytest.approx(
        sk.quantile(0.99), rel=1e-9)
    # pNN beyond the precomputed fields re-hydrates the sketch
    assert cr._get(doc, base + ".p75") == pytest.approx(
        sk.quantile(0.75), rel=1e-9)
    assert cr._get(doc, base + ".count") == 4
    assert cr._get(doc, base + ".mean") == pytest.approx(sk.mean)
    assert cr._get(doc, base + ".nope") is None
    assert cr._get(doc, "registry.merged.frontend.absent.p99") is None


# --------------------------------------------------------------------------- #
# slow: the black box survives a SIGKILL'd real worker
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_flight_recorder_survives_worker_sigkill(tmp_path):
    """Kill a real worker mid-decode; the frontend reconstructs its
    last-seconds span timeline from the shared-domain journal — the
    observability acceptance criterion."""
    from repro.serve.fleet import FleetFrontend, WorkerSpec
    from repro.serve.fleet.worker import WorkerHandle

    from repro.configs import get_config

    spec = WorkerSpec(shared_root=str(tmp_path), name="wkill",
                      arch=get_config("phi3-mini-3.8b").reduced(), slots=2,
                      max_len=64, page_tokens=4, quantum=3,
                      hb_interval_s=0.05)
    w = WorkerHandle.launch(spec)
    try:
        w.wait_ready()
        rng = np.random.default_rng(11)
        w.submit("r1", rng.integers(0, 1000, size=8).tolist(), max_new=40)
        # run until tokens stream back AND at least one heartbeat flush
        # has landed in the shared domain, then kill mid-decode
        tier = SharedTier(Path(str(tmp_path)) / "domain",
                          capacity_bytes=spec.shared_capacity)
        seen = 0
        deadline = time.time() + 180.0
        while time.time() < deadline:
            seen += sum(len(m.get("tokens", [])) for m in w.messages()
                        if m.get("op") == "tokens")
            if seen >= 4 and read_flight(tier, "wkill")[0]:
                break
            time.sleep(0.01)
        assert seen >= 4, "worker never started decoding"
        w.kill()
        assert not w.alive()

        fe = FleetFrontend([w])
        post = fe.postmortem(0, last=64)
        assert post["worker"] == "wkill"
        names = {r["name"] for r in post["records"]}
        assert "step" in names               # decode steps made it out
        assert names & {"submit", "prefill", "prefix_match"}
        assert all(r["proc"] == "wkill" for r in post["records"])
        # torn final record: the same read path tolerates a mid-append
        # kill — only the torn line drops, the timeline stays readable
        before = len(fe.postmortem(0)["records"])
        tier.append(flight_key("wkill"), b'{"name":"step","ph":"X","ts":9')
        post2 = fe.postmortem(0)
        assert post2["torn"] == post["torn"] + 1
        assert len(post2["records"]) == before
    finally:
        w.stop()
