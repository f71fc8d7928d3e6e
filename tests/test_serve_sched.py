"""Serving subsystem: hit-rate promotion, KV paging, continuous batching,
multi-stream kill/restore, and the failure-history checkpoint policy."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.policy import FailureHistoryPolicy, PolicyContext
from repro.api.session import ResilienceSession
from repro.cluster.topology import VirtualCluster
from repro.configs import get_config
from repro.core.scr import Strategy
from repro.io.beeond import CacheFS
from repro.io.serialization import serialize_state
from repro.memory.stack import HitRatePromotion, TierStack
from repro.memory.tiers import CapacityError, MemoryTier, TierKind, TierSpec
from repro.models.registry import get_model
from repro.obs.trace import Tracer
from repro.serve.kvpage import KVPager
from repro.serve.scheduler import (PagedServeScheduler, ServeScheduler,
                                   StreamState)


def mem_tier(capacity=10**9):
    return MemoryTier(TierSpec(TierKind.DRAM, capacity, 1e9, 1e9, 1e-6))


def two_level(cache_capacity=200, promotion=None, admission_fraction=None):
    cache, glob = mem_tier(cache_capacity), mem_tier()
    stack = TierStack([("cache", cache), ("global", glob)],
                      promotion=promotion, admission_fraction=admission_fraction)
    return stack, cache, glob


# ---------------------------------------------------------------------- #
# hit-rate-driven promotion (memory/stack.py)
# ---------------------------------------------------------------------- #


def test_promotes_only_after_k_hits():
    stack, cache, glob = two_level(promotion=HitRatePromotion(k=3, window=100))
    glob.put("k", b"cold-data")
    for expect_cached in (False, False, True):   # 3rd hit crosses k
        stack.get("k")
        assert cache.exists("k") == expect_cached
    assert stack.stats["promotions"] == 1


def test_hits_outside_window_do_not_promote():
    stack, cache, glob = two_level(promotion=HitRatePromotion(k=2, window=2))
    glob.put("k", b"v")
    glob.put("other", b"w")
    stack.get("k")
    stack.get("other")          # ages the window...
    stack.get("other")          # ...past k's first hit ('other' itself
    assert cache.exists("other")  # earns promotion with 2 in-window hits)
    stack.get("k")              # only 1 hit inside the window: stays cold
    assert not cache.exists("k")


def test_explicit_promote_bypasses_hit_gate():
    stack, cache, glob = two_level(promotion=HitRatePromotion(k=5, window=100))
    glob.put("k", b"v")
    stack.get("k", promote=True)
    assert cache.exists("k")


def test_observer_read_does_not_log_hits():
    stack, cache, glob = two_level(promotion=HitRatePromotion(k=2, window=100))
    glob.put("k", b"v")
    stack.get("k", promote=False)    # checkpoint-path observer read
    stack.get("k")                   # first *logged* hit
    assert not cache.exists("k")
    stack.get("k")                   # second logged hit: promote
    assert cache.exists("k")


def test_cold_blocks_demote_before_warm_ones():
    """A warm block (recent window hits) survives pressure even when LRU
    recency says otherwise: the cold block is demoted first."""
    stack, cache, glob = two_level(cache_capacity=100)
    stack.put("hot", b"h" * 40)
    stack.put("cold", b"c" * 40)
    stack.get("hot")
    stack.get("hot")
    stack.get("cold")       # cold is the most RECENT access (LRU-warmest)...
    stack.put("new", b"n" * 40)   # ...but has fewer window hits: demoted
    assert cache.exists("hot")
    assert not cache.exists("cold")
    assert glob.get("cold") == b"c" * 40


def test_stats_is_mapping_and_callable_with_miss_counters():
    stack, cache, glob = two_level()
    glob.put("k", b"v")
    stack.get("k")
    snap = stack.stats()
    assert isinstance(snap, dict)
    assert snap["misses_cache"] == 1 and snap["hits_global"] == 1
    assert stack.stats["misses_cache"] == 1   # mapping access still works
    stack.get("k")
    assert stack.stats()["hits_cache"] == 1


def test_cachefs_fill_respects_admission_control():
    """Regression: read-promotion through a cache-domain level must obey
    admission_fraction — an oversized value read through the CacheFS used
    to land in the cache unconditionally via get()'s implicit fill."""
    local, glob = mem_tier(100), mem_tier()
    fs = CacheFS(local, glob, mode="local-only")
    stack = TierStack([("beeond", fs), ("global", glob)],
                      admission_fraction=0.5)
    glob.put("big", b"B" * 60)       # fits the 100-byte cache raw...
    assert stack.get("big") == b"B" * 60
    assert not fs.cached("big"), "fill bypassed admission control"
    assert stack.stats["promotions"] == 0
    glob.put("small", b"s" * 20)     # within the admission fraction
    assert stack.get("small") == b"s" * 20
    assert fs.cached("small")
    assert stack.stats["promotions"] == 1


# ---------------------------------------------------------------------- #
# KVPager (serve/kvpage.py)
# ---------------------------------------------------------------------- #


def lane_like():
    return {
        "k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "v": jnp.ones((2, 5), jnp.bfloat16) * 1.5,
        "pos": np.int32(7),
    }


def test_pager_park_fetch_roundtrip_bytes():
    pager = KVPager.for_capacity(fast_bytes=1 << 20, page_bytes=64)
    lane = lane_like()
    nbytes = pager.park(3, lane)
    assert nbytes == serialize_state(lane).nbytes
    assert pager.is_parked(3) and pager.parked_sids() == [3]
    got = pager.fetch(3, lane_like())
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(lane)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not pager.is_parked(3)    # fetch releases by default
    pager.close()


def test_pager_oversized_lane_routed_past_fast_tier():
    lane = lane_like()
    nbytes = serialize_state(lane).nbytes
    pager = KVPager.for_capacity(fast_bytes=2 * nbytes, page_bytes=4 * nbytes,
                                 admission_fraction=0.25)
    pager.park(0, lane)              # single page > 25% of fast: routed down
    assert pager.stack.stats["admission_routed"] >= 1
    assert pager.level_used()["hbm"] == 0
    pager.close()


def test_pager_unpaged_park_is_all_or_nothing():
    lane = lane_like()
    nbytes = serialize_state(lane).nbytes
    pager = KVPager.for_capacity(fast_bytes=int(1.5 * nbytes), paged=False,
                                 page_bytes=max(1, nbytes // 4))
    pager.park(0, lane)
    other = lane_like()
    other["k"] = other["k"] + 1.0    # distinct content: no page dedups
    before = pager.pooled_pages()
    with pytest.raises(CapacityError):
        pager.park(1, other)         # no lower tier to spill to
    # the failed park left no partial pages (or references) behind
    assert pager.pooled_pages() == before
    assert pager.parked_sids() == [0]
    pager.close()


def test_pager_identical_content_parks_share_pages():
    """Content-addressed pool: two streams with byte-identical lanes hold
    references to ONE set of pooled pages — a second park moves no bytes
    (and fits where a second copy would not)."""
    lane = lane_like()
    nbytes = serialize_state(lane).nbytes
    pager = KVPager.for_capacity(fast_bytes=int(1.5 * nbytes), paged=False,
                                 page_bytes=max(1, nbytes // 4))
    pager.park(0, lane)
    put_before = pager.stats()["kv_pages_put"]
    pager.park(1, lane_like())       # same bytes: pure reference bump
    assert pager.stats()["kv_pages_put"] == put_before
    assert pager.stats()["kv_page_dedup_hits"] > 0
    assert pager.pooled_bytes() < pager.parked_bytes()
    # releasing one stream keeps the shared pages for the other
    pager.release(0)
    got = pager.fetch(1, lane_like())
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(lane)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert pager.pooled_pages() == 0  # last reference dropped on fetch
    pager.close()


def test_resume_retains_baseline_so_repark_skips_clean_pages():
    """The round-robin cycle: park -> resume (release=False) -> park.
    The resume retains the table as a dirty-tracking baseline, so the
    second park re-puts nothing for unchanged bytes."""
    pager = KVPager.for_capacity(fast_bytes=1 << 20, page_bytes=64)
    lane = lane_like()
    pager.park(5, lane)
    got = pager.fetch(5, lane_like(), release=False)   # resume into a slot
    assert not pager.is_parked(5)          # not parked: it is decoding
    assert pager.parked_sids() == []
    assert pager.table_sids() == [5]       # ...but the baseline is live
    put_before = pager.stats()["kv_pages_put"]
    pager.park(5, got)                     # quantum expired, nothing decoded
    st = pager.stats()
    assert st["kv_pages_put"] == put_before
    assert st["kv_clean_page_skips"] > 0
    assert pager.is_parked(5)
    pager.release(5)
    assert pager.pooled_pages() == 0
    pager.close()


def test_pager_repark_skips_clean_pages():
    """Per-page dirty tracking: re-parking a stream whose bytes did not
    change re-puts nothing (content hash compare), counted in stats()."""
    pager = KVPager.for_capacity(fast_bytes=1 << 20, page_bytes=64)
    lane = lane_like()
    pager.park(5, lane)
    put_before = pager.stats()["kv_pages_put"]
    pager.park(5, lane_like())       # byte-identical re-park
    st = pager.stats()
    assert st["kv_pages_put"] == put_before
    assert st["kv_clean_page_skips"] > 0
    # a genuinely dirty page is re-put; clean neighbours still skip
    # (only `pos` changes — it lives in the last page, `k`'s page is clean)
    dirty = lane_like()
    dirty["pos"] = np.int32(9)
    pager.park(5, dirty)
    st2 = pager.stats()
    assert st2["kv_pages_put"] > put_before
    assert st2["kv_clean_page_skips"] > st["kv_clean_page_skips"]
    got = pager.fetch(5, lane_like())
    assert int(got["pos"]) == 9
    pager.close()


# ---------------------------------------------------------------------- #
# ServeScheduler (serve/scheduler.py)
# ---------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def served_model():
    cfg = get_config("phi3-mini-3.8b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def reference_decode(cfg, model, params, prompt, max_new, max_len):
    """Independent greedy batch-1 decode loop (no scheduler machinery)."""
    cache = model.init_cache(cfg, 1, max_len)
    toks = list(prompt)
    pos = 0
    out = []
    while len(out) < max_new and pos < max_len:
        tok = toks[pos]
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32), jnp.int32(pos), cfg)
        pos += 1
        if pos >= len(prompt):
            nxt = int(np.asarray(logits.argmax(axis=-1))[0])
            toks.append(nxt)
            out.append(nxt)
    return out


def make_paged_scheduler(cfg, model, params, slots, max_len, session=None,
                         quantum=3, fast_lanes=3):
    lane_bytes = serialize_state(
        jax.device_get(model.init_cache(cfg, 1, max_len))).nbytes
    pager = KVPager.for_capacity(fast_bytes=fast_lanes * lane_bytes,
                                 page_bytes=max(1024, lane_bytes // 4))
    return ServeScheduler(cfg, model, params, slots=slots, max_len=max_len,
                          pager=pager, session=session, quantum=quantum)


def test_oversubscribed_paged_decode_matches_reference(served_model):
    """8 streams over 2 slots with parking/resume through the tier stack:
    every stream's output must equal an independent batch-1 decode."""
    cfg, model, params = served_model
    max_len, max_new = 24, 5
    sched = make_paged_scheduler(cfg, model, params, slots=2, max_len=max_len)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(2, 7)))
               for _ in range(8)]
    sids = [sched.submit(p, max_new=max_new) for p in prompts]
    sched.run()
    assert sched.stats["parked"] > 0, "oversubscription must exercise paging"
    assert sched.stats["max_resident"] == 8
    for sid, prompt in zip(sids, prompts):
        want = reference_decode(cfg, model, params, list(prompt), max_new,
                                max_len)
        assert sched.output(sid) == want, f"stream {sid} diverged"
    sched.close()


def _holds(outer, inner):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_paged_step_phases_tile_the_step_and_count_queue_wait(served_model):
    """A step with an admission: step > schedule > admit > call_prefill,
    then feed, call_decode, wait and commit in order inside the step;
    each fresh admission adds its wait since submit to queue_wait_s."""
    cfg, model, params = served_model
    tr = Tracer()
    sched = PagedServeScheduler(cfg, model, params, slots=2, max_len=24,
                                page_tokens=4, tracer=tr)
    sid = sched.submit(list(range(1, 11)), max_new=4)
    sched.step()
    spans = [r for r in tr.records() if r["ph"] == "X"]
    # the ring holds spans in the order they end
    # 9 prompt tokens prefill in one PREFILL_CHUNK call
    assert [r["name"] for r in spans] == [
        "call_prefill", "admit", "schedule", "feed",
        "call_decode", "wait", "commit", "step"]
    by = {r["name"]: r for r in spans}
    assert _holds(by["step"], by["schedule"])
    assert _holds(by["schedule"], by["admit"])
    assert all(_holds(by["admit"], r) for r in spans
               if r["name"] == "call_prefill")
    phases = [by[n] for n in ("schedule", "feed", "call_decode", "wait",
                              "commit")]
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert all(_holds(by["step"], r) for r in phases)
    # 10 prompt tokens: 9 prefilled, the last runs in the slot
    assert by["admit"]["tid"] == sid
    assert by["admit"]["args"] == {"kind": "fresh", "tokens": 9, "saved": 0}
    assert sched.stats["joined"] == 1
    first = sched.stats["queue_wait_s"]
    assert first > 0

    # a plain step holds no admission; a later request's wait is counted
    tr.clear()
    sched.step()
    assert [r["name"] for r in tr.records()] == [
        "schedule", "feed", "call_decode", "wait", "commit", "step"]
    sched.submit([3, 4, 5], max_new=2)
    time.sleep(0.05)
    sched.step()
    assert sched.stats["joined"] == 2
    assert sched.stats["queue_wait_s"] >= first + 0.05
    sched.close()


def test_contiguous_admission_is_one_admit_span(served_model):
    cfg, model, params = served_model
    tr = Tracer()
    sched = ServeScheduler(cfg, model, params, slots=1, max_len=16,
                           tracer=tr)
    sid = sched.submit([5, 6, 7], max_new=2)
    sched.step()
    (admit,) = tr.records("admit")
    assert admit["tid"] == sid and admit["args"] == {"kind": "fresh"}
    assert sched.stats["joined"] == 1 and sched.stats["queue_wait_s"] > 0
    assert tr.records("prefill") == []
    sched.close()


def test_unpaged_fast_tier_limits_residency(served_model):
    cfg, model, params = served_model
    max_len = 24
    lane_bytes = serialize_state(
        jax.device_get(model.init_cache(cfg, 1, max_len))).nbytes
    kw = dict(slots=2, max_len=max_len, quantum=2)

    def run_one(paged):
        pager = KVPager.for_capacity(fast_bytes=3 * lane_bytes, paged=paged,
                                     page_bytes=max(1024, lane_bytes // 4))
        sched = ServeScheduler(cfg, model, params, pager=pager, **kw)
        rng = np.random.default_rng(5)
        for _ in range(7):
            sched.submit(rng.integers(0, cfg.vocab_size, size=4), max_new=4)
        sched.run()
        stats = dict(sched.stats)
        outs = {sid: sched.output(sid) for sid in sched.streams}
        sched.close()
        return stats, outs

    flat_stats, flat_outs = run_one(paged=False)
    paged_stats, paged_outs = run_one(paged=True)
    assert flat_stats["park_failures"] > 0
    assert paged_stats["park_failures"] == 0
    assert paged_stats["max_resident"] == 7
    assert paged_stats["max_resident"] > flat_stats["max_resident"]
    assert flat_outs == paged_outs   # placement never changes the tokens


def test_multi_stream_kill_restore_byte_identity(served_model, tmp_path):
    """Mid-decode kill with streams active, parked, waiting and done; a
    FRESH scheduler restores the stream set from the checkpoint alone and
    finishes every stream byte-identically."""
    cfg, model, params = served_model
    max_len, max_new, slots = 24, 5, 2
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(2, 7)))
               for _ in range(8)]

    ref = make_paged_scheduler(cfg, model, params, slots, max_len)
    for p in prompts:
        ref.submit(p, max_new=max_new)
    ref.run()
    want = {sid: ref.output(sid) for sid in ref.streams}
    ref.close()

    cluster = VirtualCluster(4, 0, root=tmp_path)
    with ResilienceSession.for_cluster(cluster, strategy=Strategy.XOR,
                                       procs_per_node=2) as session:
        s1 = make_paged_scheduler(cfg, model, params, slots, max_len,
                                  session=session)
        for p in prompts:
            s1.submit(p, max_new=max_new)
        s1.run(max_steps=9)
        states = {s.state for s in s1.streams.values()}
        assert StreamState.PARKED in states, "kill point must have parked streams"
        s1.save()
        saved_step = s1.step_count
        s1.close()

        s2 = make_paged_scheduler(cfg, model, params, slots, max_len,
                                  session=session)
        got_step = s2.restore()
        assert got_step == saved_step
        s2.run()
        assert {sid: s2.output(sid) for sid in s2.streams} == want
        s2.close()


def test_engine_decode_tolerates_extra_scheduler_streams(served_model):
    """Regression: a caller may run extra streams through `.scheduler`;
    the engine's lockstep decode must only read its own rows and stop
    cleanly when they finish (it used to KeyError on the foreign sid)."""
    from repro.serve.engine import ServeEngine

    cfg, model, params = served_model
    eng = ServeEngine(cfg, model, params, batch=2, max_len=16)
    eng.prefill(jnp.zeros((2, 3), jnp.int32))
    eng.scheduler.submit([1, 2], max_new=2)    # foreign short stream
    out = eng.decode(50)
    assert len(out) == 16 - 3                  # engine rows ran to max_len
    assert all(o.shape == (2,) for o in out)
    eng.close()


def test_restore_rejects_mismatched_geometry(served_model, tmp_path):
    cfg, model, params = served_model
    cluster = VirtualCluster(4, 0, root=tmp_path)
    with ResilienceSession.for_cluster(cluster, strategy=Strategy.XOR,
                                       procs_per_node=2) as session:
        s1 = make_paged_scheduler(cfg, model, params, slots=2, max_len=24,
                                  session=session)
        s1.submit([1, 2, 3], max_new=2)
        s1.run(max_steps=2)
        s1.save()
        s1.close()
        s2 = make_paged_scheduler(cfg, model, params, slots=4, max_len=24,
                                  session=session)
        with pytest.raises(ValueError, match="slots=2"):
            s2.restore()
        s2.close()


# ---------------------------------------------------------------------- #
# FailureHistoryPolicy (api/policy.py)
# ---------------------------------------------------------------------- #


def test_failure_history_ema_tracks_gaps():
    p = FailureHistoryPolicy(mtbf_s=1000.0, ema=0.5)
    p.observe_failure(0.0)
    assert p.mtbf_estimate_s == 1000.0   # first failure: no gap yet
    p.observe_failure(100.0)             # gap 100 -> 0.5*1000 + 0.5*100
    assert p.mtbf_estimate_s == pytest.approx(550.0)
    p.observe_failure(150.0)             # gap 50
    assert p.mtbf_estimate_s == pytest.approx(300.0)
    assert p.failures_observed == 3


def test_failure_history_dedupes_same_incident_reports():
    """The trainer invalidates a node at the failure AND after recovery;
    the second report lands within min_gap_s and must not fold a
    near-zero gap into the MTBF estimate."""
    p = FailureHistoryPolicy(mtbf_s=1000.0, ema=0.5, min_gap_s=1.0)
    p.observe_failure(0.0)
    p.observe_failure(0.010)             # recovery-side duplicate: ignored
    assert p.failures_observed == 1
    assert p.mtbf_estimate_s == 1000.0
    p.observe_failure(200.0)             # a genuinely separate incident
    assert p.failures_observed == 2
    assert p.mtbf_estimate_s == pytest.approx(600.0)


def test_failure_history_tightens_and_loosens_engine_knobs():
    p = FailureHistoryPolicy(mtbf_s=3600.0, ema=1.0, min_keep=2, max_keep=8,
                             max_flush_every=4, tight_mtbf_s=60.0,
                             loose_mtbf_s=86400.0)
    # frequent failures: full paranoia
    p.observe_failure(0.0)
    p.observe_failure(10.0)
    assert p.engine_hints() == {"keep": 8, "flush_every": 1}
    # failures a day apart: fully relaxed
    p2 = FailureHistoryPolicy(mtbf_s=3600.0, ema=1.0, min_keep=2, max_keep=8,
                              max_flush_every=4, tight_mtbf_s=60.0,
                              loose_mtbf_s=86400.0)
    p2.observe_failure(0.0)
    p2.observe_failure(90000.0)
    assert p2.engine_hints() == {"keep": 2, "flush_every": 4}
    # cadence comes from Daly at the live MTBF estimate
    assert p.should_checkpoint(PolicyContext(step=1, now_s=0.0))  # bootstrap


def test_session_applies_failure_history_hints(tmp_path):
    cluster = VirtualCluster(4, 0, root=tmp_path)
    # seeded below tight_mtbf_s: the policy starts paranoid, and the
    # session must push those knobs into the engine at the first
    # failure-observation point
    policy = FailureHistoryPolicy(mtbf_s=30.0, tight_mtbf_s=60.0,
                                  loose_mtbf_s=86400.0, max_keep=8)
    with ResilienceSession.for_cluster(cluster, policy=policy,
                                       procs_per_node=2) as session:
        baseline = session.scr.keep
        session.invalidate_node(1)
        assert policy.failures_observed == 1
        assert session.scr.keep == 8
        assert session.scr.flush_every == 1
        assert session.scr.keep >= baseline
        # the recovery-side re-invalidation of the same incident is
        # deduplicated, not folded into the MTBF estimate
        session.invalidate_node(1)
        assert policy.failures_observed == 1
