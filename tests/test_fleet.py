"""Serving fleet: prefix publish/subscribe, tail sharing, admission.

Layered like the subsystem: synthetic-layout tests for the trie
mechanics (no jax model, milliseconds), stub-worker tests for the
front-end's admission logic, and slow end-to-end tests spawning real
worker processes over one shared domain.
"""

import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.memory.codecs import CodecRule, make_codec
from repro.memory.shared import SharedTier
from repro.memory.stack import HitRatePromotion, KeyClass, TierStack
from repro.memory.tiers import MemoryTier, TierKind, TierSpec
from repro.serve.fleet.board import PrefixBoard
from repro.serve.prefix import LaneLayout, PrefixCache, prefix_page_key

MAX_LEN, PT = 16, 4


TEMPLATE = {"k": np.zeros((2, 1, MAX_LEN, 2, 4), np.float32),
            "v": np.zeros((2, 1, MAX_LEN, 2, 4), np.float32)}


def make_layout():
    axes = {"k": ("layers", "batch", "kv_seq", "heads", "head_dim"),
            "v": ("layers", "batch", "kv_seq", "heads", "head_dim")}
    return LaneLayout(TEMPLATE, axes)


def make_stack(shared=None, fast_bytes=1 << 20, codec=None):
    levels = [("hbm", MemoryTier(TierSpec(TierKind.HBM, fast_bytes,
                                          450e9, 450e9, 1e-7)))]
    if shared is not None:
        levels.append(("shared", shared))
    else:
        levels.append(("global", MemoryTier(TierSpec(
            TierKind.GLOBAL, 1 << 30, 5e9, 5e9, 5e-4))))
    return TierStack(levels, promotion=HitRatePromotion(k=2, window=64),
                     codecs={KeyClass.KV: CodecRule(codec)} if codec else None)


def rand_lane(layout, rng):
    return {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in TEMPLATE.items()}


# --------------------------------------------------------------------------- #
# publish / subscribe: export_records + adopt_nodes
# --------------------------------------------------------------------------- #

def publish(cache, stack_to, published):
    fresh = []
    for rec in cache.export_records():
        if rec["digest"] in published:
            continue
        payload = cache.stack.get(prefix_page_key(rec["digest"]),
                                  promote=False)
        stack_to.put_at("shared", prefix_page_key(rec["digest"]), payload)
        published.add(rec["digest"])
        fresh.append(rec)
    return fresh


def test_adopt_nodes_cross_cache(tmp_path):
    """B adopts A's records and reads the payloads through the shared
    level — the in-process model of two fleet workers."""
    layout, rng = make_layout(), np.random.default_rng(0)
    dom = SharedTier(tmp_path / "dom")
    a = PrefixCache(make_stack(shared=dom), layout, page_tokens=PT)
    b = PrefixCache(make_stack(shared=SharedTier(tmp_path / "dom")),
                    layout, page_tokens=PT)
    tokens = list(range(12))
    lane = rand_lane(layout, rng)
    a.extend(tokens, 8, lane)                      # two full pages
    recs = publish(a, a.stack, set())
    assert len(recs) == 2
    assert b.adopt_nodes(recs) == 2
    assert b.stats["nodes_adopted"] == 2
    covered, path = b.match(tokens)
    assert covered == 8 and len(path) == 2
    # payload readable through B's stack (shared level hit), content-equal
    part = b.read_node_part(path[0])
    np.testing.assert_array_equal(part["k"], layout.extract(lane, 0, PT)["k"])
    assert b.stack.stats()["hits_shared"] >= 1


def test_adopt_skips_duplicates_and_orphans(tmp_path):
    layout, rng = make_layout(), np.random.default_rng(1)
    a = PrefixCache(make_stack(), layout, page_tokens=PT)
    b = PrefixCache(make_stack(), layout, page_tokens=PT)
    a.extend(list(range(8)), 8, rand_lane(layout, rng))
    recs = a.export_records()
    assert b.adopt_nodes(recs) == 2
    assert b.adopt_nodes(recs) == 0               # idempotent
    orphan = dict(recs[1], digest="feedfacefeedfacefeedface",
                  parent="0" * 24, chunk=[99, 98, 97, 96])
    assert b.adopt_nodes([orphan]) == 0           # unknown parent skipped
    assert len(b) == 2


def test_adopted_nodes_count_toward_budget_and_evict(tmp_path):
    layout, rng = make_layout(), np.random.default_rng(2)
    a = PrefixCache(make_stack(), layout, page_tokens=PT)
    a.extend(list(range(8)), 8, rand_lane(layout, rng))
    recs = a.export_records()
    nbytes = sum(r["nbytes"] for r in recs)
    b = PrefixCache(make_stack(), layout, page_tokens=PT,
                    capacity_bytes=nbytes)        # exactly fits
    assert b.adopt_nodes(recs) == 2
    assert b.cached_bytes() == nbytes
    # pressure: a locally inserted chain evicts the adopted tail
    b.extend(list(range(100, 108)), 8, rand_lane(layout, rng))
    assert b.cached_bytes() <= nbytes
    assert b.stats["pages_evicted"] >= 1


def test_export_records_orders_parents_first():
    layout, rng = make_layout(), np.random.default_rng(3)
    a = PrefixCache(make_stack(), layout, page_tokens=PT)
    a.extend(list(range(12)), 12, rand_lane(layout, rng))
    recs = a.export_records()
    seen = set()
    for rec in recs:
        assert rec["parent"] == "" or rec["parent"] in seen
        seen.add(rec["digest"])


# --------------------------------------------------------------------------- #
# PrefixBoard
# --------------------------------------------------------------------------- #

def test_board_publish_poll_roundtrip(tmp_path):
    a, b = PrefixBoard(tmp_path), PrefixBoard(tmp_path)
    recs = [{"digest": "d1", "parent": "", "chunk": [1, 2], "end": 2,
             "nbytes": 10, "crc32": 7}]
    assert a.publish(recs) == 1
    assert b.poll() == recs
    assert b.poll() == []                         # cursor advanced
    a.publish([dict(recs[0], digest="d2", parent="d1")])
    got = b.poll()
    assert [r["digest"] for r in got] == ["d2"]
    # a's own cursor sees everything it published too
    assert [r["digest"] for r in a.poll()] == ["d1", "d2"]


def test_board_ignores_torn_tail(tmp_path):
    a, b = PrefixBoard(tmp_path), PrefixBoard(tmp_path)
    a.publish([{"digest": "d1", "parent": "", "chunk": [], "end": 0,
                "nbytes": 0, "crc32": 0}])
    with open(a.path, "ab") as f:
        f.write(b'{"digest": "partial')          # torn concurrent append
    got = b.poll()
    assert [r["digest"] for r in got] == ["d1"]  # whole lines only
    assert b.poll() == []


def test_board_empty_poll(tmp_path):
    assert PrefixBoard(tmp_path).poll() == []


# --------------------------------------------------------------------------- #
# satellite 1: partial-page tail sharing (synthetic layout)
# --------------------------------------------------------------------------- #

def test_register_and_match_tail():
    layout, rng = make_layout(), np.random.default_rng(4)
    cache = PrefixCache(make_stack(), layout, page_tokens=PT)
    tokens = list(range(10))                      # 2 pages + 2-token tail
    lane = rand_lane(layout, rng)
    cache.extend(tokens, 8, lane)
    node = cache.register_tail(tokens, 10, lane)
    assert node is not None and node.end == 10 and len(node.chunk) == 2
    assert cache.stats["tail_pages_inserted"] == 1
    # same-prefix request with a longer suffix reuses the tail
    req = tokens + [77, 78, 79]
    covered, path = cache.match(req)
    assert covered == 8
    tail = cache.match_tail(req, covered, path)
    assert tail is node
    part = cache.read_node_part(tail)
    np.testing.assert_array_equal(
        part["k"], layout.extract(lane, 8, 10)["k"])
    assert cache.stats["tail_hits"] == 1
    assert cache.stats["tail_tokens_reused"] == 2


def test_tail_requires_full_page_ancestors():
    layout, rng = make_layout(), np.random.default_rng(5)
    cache = PrefixCache(make_stack(), layout, page_tokens=PT)
    lane = rand_lane(layout, rng)
    # no full pages cached for this chain -> tail refuses to anchor
    assert cache.register_tail(list(range(10)), 10, lane) is None
    cache.extend(list(range(8)), 8, lane)
    assert cache.register_tail(list(range(8)), 8, lane) is None  # no tail


def test_match_tail_prefers_longest():
    layout, rng = make_layout(), np.random.default_rng(6)
    cache = PrefixCache(make_stack(), layout, page_tokens=PT)
    tokens = list(range(8))
    lane = rand_lane(layout, rng)
    cache.extend(tokens, 8, lane)
    cache.register_tail(tokens + [50], 9, lane)
    cache.register_tail(tokens + [50, 51], 10, lane)
    tail = cache.match_tail(tokens + [50, 51, 52], 8,
                            cache.match(tokens)[1])
    assert tail.end == 10


def test_tail_mismatch_not_matched():
    layout, rng = make_layout(), np.random.default_rng(7)
    cache = PrefixCache(make_stack(), layout, page_tokens=PT)
    tokens = list(range(8))
    lane = rand_lane(layout, rng)
    cache.extend(tokens, 8, lane)
    cache.register_tail(tokens + [50, 51], 10, lane)
    assert cache.match_tail(tokens + [60, 61], 8,
                            cache.match(tokens)[1]) is None
    # suffix shorter than the tail cannot use it either
    assert cache.match_tail(tokens + [50], 8, cache.match(tokens)[1]) is None


# --------------------------------------------------------------------------- #
# satellite 2: quantized prefix pages survive demotion
# --------------------------------------------------------------------------- #

def test_quantized_prefix_page_readable_after_demotion():
    """Int8 kv codec: a prefix payload demoted past the fast level
    decodes to different bytes; the fetch path must re-anchor integrity
    to the decoded stream instead of failing the insert-time crc."""
    layout, rng = make_layout(), np.random.default_rng(8)
    part_bytes = 2 * 2 * 1 * PT * 2 * 4 * 4
    stack = make_stack(fast_bytes=int(part_bytes * 1.5),
                       codec=make_codec("int8", dtype="float32", block=4))
    cache = PrefixCache(stack, layout, page_tokens=PT)
    lane = rand_lane(layout, rng)
    path = cache.extend(list(range(12)), 8, lane)
    cache.extend(list(range(100, 112)), 8, rand_lane(layout, rng))
    st = stack.stats()
    # pressure really demoted payloads: evictions moved them down
    # through the int8 codec (encoded bytes < plaintext)
    assert st["evictions"] >= 1
    assert 0 < st["kv_bytes_encoded_out"] < st["kv_bytes_encoded"]
    part = cache.read_node_part(path[0])          # would IOError before fix
    np.testing.assert_allclose(
        part["k"], layout.extract(lane, 0, PT)["k"], rtol=0.1, atol=0.05)
    covered, p2 = cache.match(list(range(12)))
    fresh = layout.zero_lane()
    assert cache.fetch_into(p2, fresh) == 8       # nodes survive the fetch
    assert len(cache) >= 2


def test_lossless_codec_keeps_strict_crc():
    layout, rng = make_layout(), np.random.default_rng(9)
    stack = make_stack(codec=make_codec("zlib"))
    cache = PrefixCache(stack, layout, page_tokens=PT)
    lane = rand_lane(layout, rng)
    path = cache.extend(list(range(8)), 8, lane)
    part = cache.read_node_part(path[0])
    np.testing.assert_array_equal(part["k"], layout.extract(lane, 0, PT)["k"])


# --------------------------------------------------------------------------- #
# front-end admission logic (stub workers)
# --------------------------------------------------------------------------- #

class StubWorker:
    """Pipe-free WorkerHandle stand-in: finishes a request after
    ``delay_pumps`` message polls."""

    def __init__(self, delay_pumps=1):
        self.submitted = []
        self._pending = []
        self.delay = delay_pumps

    def submit(self, rid, prompt, max_new, weight=1):
        self.submitted.append({"rid": rid, "prompt": list(prompt),
                               "max_new": max_new, "weight": weight})
        self._pending.append([self.delay, rid, max_new])

    def messages(self):
        out = []
        for ent in list(self._pending):
            ent[0] -= 1
            if ent[0] <= 0:
                out.append({"op": "done", "rid": ent[1],
                            "tokens": [0] * ent[2]})
                self._pending.remove(ent)
        return out

    def stats(self):
        return {}

    def stop(self):
        pass


def make_frontend(n_workers=2, **kw):
    from repro.serve.fleet.frontend import FleetFrontend
    workers = [StubWorker() for _ in range(n_workers)]
    return FleetFrontend(workers, **kw), workers


def test_quota_throttles_only_the_noisy_tenant():
    from repro.serve.fleet.frontend import TenantQuota
    fe, workers = make_frontend(
        1, quotas={"noisy": TenantQuota(1)},
        default_quota=TenantQuota(8))
    noisy = [fe.submit([1, 2], 3, tenant="noisy") for _ in range(4)]
    quiet = [fe.submit([1, 2], 3, tenant="quiet") for _ in range(2)]
    fe.pump()
    w = workers[0]
    # one noisy dispatch (quota 1), both quiet dispatches, throttling seen
    assert sum(1 for s in w.submitted if s["rid"] in noisy) == 1
    assert sum(1 for s in w.submitted if s["rid"] in quiet) == 2
    assert fe.stats["throttle_events"] >= 1
    fe.wait(noisy + quiet, timeout=10)
    assert fe.stats["completed"] == 6             # backlog drains eventually
    assert all(len(fe.result(r)) == 3 for r in noisy)


def test_priority_class_maps_to_quantum_weight():
    from repro.serve.fleet.frontend import PriorityClass
    fe, workers = make_frontend(
        1, classes={"lo": PriorityClass("lo", 1),
                    "hi": PriorityClass("hi", 3)})
    fe.submit([1], 2, prio="hi")
    fe.submit([1], 2, prio="lo")
    fe.pump()
    assert [s["weight"] for s in workers[0].submitted] == [3, 1]
    with pytest.raises(ValueError):
        fe.submit([1], 2, prio="nope")


def test_least_loaded_routing():
    from repro.serve.fleet.frontend import FleetFrontend
    workers = [StubWorker(delay_pumps=10), StubWorker(delay_pumps=10)]
    fe = FleetFrontend(workers)
    r1 = fe.submit([1] * 10, 10)                  # cost 20
    fe.pump()
    r2 = fe.submit([1], 1)                        # cost 2 -> other worker
    r3 = fe.submit([1], 1)
    fe.pump()                                     # r1 still in flight
    first = 0 if workers[0].submitted and \
        workers[0].submitted[0]["rid"] == r1 else 1
    assert [s["rid"] for s in workers[first].submitted] == [r1]
    assert {s["rid"] for s in workers[1 - first].submitted} == {r2, r3}


def test_admission_latency_recorded():
    fe, _ = make_frontend(1)
    rid = fe.submit([1], 1, tenant="t")
    fe.wait([rid], timeout=10)
    assert fe.admission_latency_p99("t") >= 0.0
    assert fe.admission_latency_p99("never-dispatched") == 0.0


# --------------------------------------------------------------------------- #
# satellite: checkpoint sessions over the shared root
# --------------------------------------------------------------------------- #

def test_session_restore_across_instances(tmp_path):
    """A checkpoint committed through one session is restorable by a
    session constructed later over the same shared root — the storage
    hierarchy lives on the shared filesystem, not in the process."""
    from repro.api import ResilienceSession

    state = {"w": np.arange(32, dtype=np.float32),
             "b": np.ones(4, np.float32)}
    with ResilienceSession.for_shared_tier(tmp_path / "fleet") as s1:
        s1.save(3, state)
        s1.wait_drained()
    with ResilienceSession.for_shared_tier(tmp_path / "fleet") as s2:
        like = {"w": np.zeros(32, np.float32), "b": np.zeros(4, np.float32)}
        got, step = s2.restore_latest(like)
    assert step == 3
    np.testing.assert_array_equal(got["w"], state["w"])
    np.testing.assert_array_equal(got["b"], state["b"])


# --------------------------------------------------------------------------- #
# slow: real workers over one shared domain
# --------------------------------------------------------------------------- #

def _run_one(w, rid, prompt, max_new=4, timeout=180.0):
    w.submit(rid, prompt, max_new=max_new)
    deadline = time.time() + timeout
    while time.time() < deadline:
        for m in w.messages():
            if m.get("op") == "done" and m["rid"] == rid:
                return m["tokens"]
        time.sleep(0.01)
    raise TimeoutError(f"request {rid} never finished")


@pytest.mark.slow
def test_cross_worker_prefix_reuse(tmp_path):
    """Worker B admits a prompt whose prefix only worker A computed:
    B adopts the published trie nodes, reads the pages out of the shared
    tier, and skips the prefill — the tentpole acceptance criterion."""
    from repro.serve.fleet import WorkerHandle, WorkerSpec

    arch = get_config("phi3-mini-3.8b").reduced()
    mk = lambda: WorkerSpec(shared_root=str(tmp_path), arch=arch, slots=2,
                            max_len=32, page_tokens=4, quantum=3)
    a, b = WorkerHandle.launch(mk()), WorkerHandle.launch(mk())
    try:
        a.wait_ready()
        b.wait_ready()
        rng = np.random.default_rng(3)
        sysp = rng.integers(0, 1000, size=13).tolist()
        # "done" implies published: A's trie nodes are on the board
        # before its completion reaches us
        _run_one(a, "a1", sysp + rng.integers(0, 1000, size=4).tolist())
        _run_one(b, "b1", sysp + rng.integers(0, 1000, size=5).tolist())
        sb = b.stats()
        assert sb["scheduler"]["prefill_tokens_saved"] > 0
        assert sb["tier"]["hits_shared"] > 0
        assert sb["prefix"]["nodes_adopted"] > 0
        # drain protocol: nothing unfinished, but the op answers
        assert b.drain() == []
    finally:
        a.stop()
        b.stop()


@pytest.mark.slow
def test_fleet_frontend_end_to_end(tmp_path):
    from repro.serve.fleet import FleetFrontend, TenantQuota, WorkerSpec

    arch = get_config("phi3-mini-3.8b").reduced()
    specs = [WorkerSpec(shared_root=str(tmp_path), arch=arch, slots=2,
                        max_len=32, page_tokens=4, quantum=3)
             for _ in range(2)]
    rng = np.random.default_rng(7)
    sysp = rng.integers(0, 1000, size=9).tolist()
    with FleetFrontend.launch(specs,
                              quotas={"noisy": TenantQuota(1)}) as fe:
        rids = [fe.submit(
            sysp + rng.integers(0, 1000, size=int(rng.integers(3, 6))).tolist(),
            max_new=4, tenant="noisy" if i % 2 else "quiet")
            for i in range(4)]
        fe.wait(rids, timeout=300)
        outs = [fe.result(r) for r in rids]
        assert all(len(o) == 4 for o in outs)
        assert fe.stats["completed"] == 4
        assert fe.stats["throttle_events"] >= 1   # noisy went over quota


def test_spawned_worker_serves_the_config_arch(tmp_path):
    """A worker builds exactly the ArchConfig its ServeConfig names — a
    caller's own config, at whatever size it is given — and reports it,
    with the device it holds, when ready."""
    import dataclasses

    from repro.serve import ServeConfig
    from repro.serve.fleet import WorkerHandle

    arch = dataclasses.replace(get_config("phi3-mini-3.8b").reduced(),
                               n_layers=1, param_dtype="bfloat16")
    cfg = ServeConfig(arch=arch, full_size=True, slots=1, max_len=16,
                      page_tokens=4)
    w = WorkerHandle.launch(cfg.worker_spec(str(tmp_path), name="w0"))
    try:
        w.wait_ready(timeout=120)
        assert w.arch == arch == cfg.resolved_arch()
        assert w.device["count"] >= 1 and w.device["platform"]
    finally:
        w.stop()
