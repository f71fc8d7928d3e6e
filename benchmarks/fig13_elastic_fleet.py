"""Fig 13 (elastic fleet): kill 1-of-N workers mid-decode, recover.

DEEP-ER's resiliency half (SCR-style multi-level checkpointing, §III)
meets the serving fleet here: every worker epoch-checkpoints its live
stream set through the shared cache domain, the front-end's failure
detector classifies a SIGKILL'd worker dead (heartbeat staleness
triggering a process-liveness probe — slow-but-alive can only go
``suspect``), and the dead worker's streams are re-admitted on the
survivors with their recovered token prefixes replayed.  Three asserted
claims:

  (a) **token identity** — every stream, migrated or not, completes
      with exactly the tokens an uninterrupted single-process run
      produces (greedy decode over the same params is a pure function
      of token history, so replaying the recovered prefix as prompt
      suffix continues the very same continuation);
  (b) **bounded survivor stall** — the p99 inter-token gap of streams
      on surviving workers, measured across the failure window, stays
      under ``hb_timeout_s`` plus a fixed recovery-work allowance (the
      kill must not freeze the rest of the fleet);
  (c) **bounded recovery stall** — a migrated stream's token gap across
      the failure is bounded by detection latency (``hb_timeout_s``)
      plus the epoch cadence (``ckpt_every`` scheduler steps — the lost
      work it may need to re-reach) plus a fixed re-admission allowance.

Observability rides the same scenario: the killed worker's flight
recorder (heartbeat-flushed span ring in the shared domain) must yield
a post-mortem decode timeline after the SIGKILL, and the artifact
embeds the fleet's merged registry snapshot plus the victim's recovered
timeline tail.

The bench drives the whole scenario through the unified serving API
(``ServeConfig`` + ``Serve.local`` for the reference run, which runs
after the fleet has stopped so the parent never holds a device its
workers need, ``Serve.fleet`` for the fleet under test) and only fires
the kill once the victim worker's post-admission ``kind="epoch"``
marker is visible on the board, so the scenario exercises
checkpoint-based recovery, not just frontend replay.

  PYTHONPATH=src python -m benchmarks.fig13_elastic_fleet [--smoke]

Emits ``BENCH_fig13_elastic_fleet.json``; CI regenerates it every run
and benchmarks/check_regression.py gates ``p99_stall_survivors`` and
``recovery_stall`` (lower-is-better) against the committed baseline.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from benchmarks.common import bench_json, row
from repro.obs.metrics import quantile
from repro.serve import Serve, ServeConfig
from repro.serve.fleet import PrefixBoard
from repro.serve.fleet.board import record_kind

ARCH = "phi3-mini-3.8b"
PAGE_TOKENS = 4
# long lanes on purpose: a decode step on the reduced model is
# milliseconds, so the failure window (detection timeout + epoch load +
# re-admission) only lands *inside* a stream's lifetime when streams
# run hundreds of tokens — exactly the regime elasticity matters in
MAX_LEN = 256
MAX_NEW = 160
CKPT_EVERY = 4          # epoch cadence in scheduler steps
HB_INTERVAL_S = 0.05
HB_TIMEOUT_S = 0.3
# fixed allowances on top of the principled terms: recovery work the
# frontend does inline (epoch restore + re-dispatch) for (b), one
# replayed-prefix prefill + quantum rotation on the survivor for (c)
SURVIVOR_SLACK_S = 4.0
RECOVERY_SLACK_S = 8.0


def _config() -> ServeConfig:
    return ServeConfig(arch=ARCH, paged=True, slots=2, max_len=MAX_LEN,
                       page_tokens=PAGE_TOKENS, quantum=3,
                       ckpt_every=CKPT_EVERY, hb_interval_s=HB_INTERVAL_S,
                       hb_timeout_s=HB_TIMEOUT_S)


def _prompts(n: int, rng) -> List[List[int]]:
    sysp = rng.integers(0, 1000, size=2 * PAGE_TOKENS).tolist()
    return [sysp + rng.integers(0, 1000,
                                size=int(rng.integers(3, 7))).tolist()
            for _ in range(n)]


def reference_tokens(cfg: ServeConfig, prompts: List[List[int]],
                     max_new: int) -> List[List[int]]:
    """The no-kill oracle: the same workload decoded in-process.  Same
    arch + seed means the same params as every fleet worker, so greedy
    decode produces the token sequences migration must reproduce."""
    srv = Serve.local(cfg)
    try:
        sids = [srv.submit(p, max_new=max_new) for p in prompts]
        srv.run()
        return [srv.output(sid) for sid in sids]
    finally:
        srv.close()


def _gaps(stamps: List[float], t_from: float) -> List[float]:
    """Inter-arrival gaps spanning the window starting at the last
    arrival <= t_from (so the gap across t_from itself is included)."""
    pre = [t for t in stamps if t <= t_from]
    post = [t for t in stamps if t > t_from]
    pts = (pre[-1:] if pre else [t_from]) + post
    return [b - a for a, b in zip(pts, pts[1:])]


def measure_elastic(tmp: Path, n_workers: int, n_streams: int,
                    max_new: int, timeout: float = 600.0) -> Dict:
    cfg = _config()
    rng = np.random.default_rng(13)
    prompts = _prompts(n_streams, rng)

    root = tmp / "elastic"
    root.mkdir(parents=True, exist_ok=True)
    fe = Serve.fleet(cfg, workers=n_workers, shared_root=str(root))
    victim_worker = 0
    w0_name = fe.workers[victim_worker].spec.name
    try:
        # warmup: one short request per worker compiles prefill/decode
        # and publishes the shared system prompt; excluded from stalls
        warm = [fe.submit(prompts[i % len(prompts)], max_new=1)
                for i in range(n_workers)]
        fe.wait(warm, timeout=timeout)
        adopted0 = [s["prefix"]["nodes_adopted"] for s in fe.worker_stats()]

        # a private board cursor watches for the victim worker's epoch
        # marker (a cheap file poll — the heavyweight load_epoch restore
        # runs once, inside the frontend's recovery)
        board = PrefixBoard(root)
        board.poll()                        # skip warmup-era records
        wall_submit = time.time()
        rids = [fe.submit(p, max_new=max_new) for p in prompts]
        arrivals: Dict[int, List[float]] = {r: [] for r in rids}
        seen = {r: 0 for r in rids}
        victims: List[int] = []
        migrated_expect: List[int] = []
        t_kill = None
        epoch_seen = False
        deadline = time.monotonic() + timeout
        while not all(seen[r] >= max_new for r in rids):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"elastic run stalled: {dict(seen)} of {max_new}")
            fe.pump()
            now = time.perf_counter()
            for r in rids:
                n = len(fe.progress(r))
                if n > seen[r]:
                    arrivals[r].extend([now] * (n - seen[r]))
                    seen[r] = n
            if t_kill is not None:
                continue
            if not victims:
                # one pump after submit every rid is dispatched (quota
                # default admits all); snapshot the victim set then
                victims = [r for r in rids
                           if fe.assignment(r) == victim_worker]
            # fire the kill only once the victim worker has committed
            # an epoch *after* main admission (so it covers the victim
            # streams) and every victim has decoded work both behind it
            # and still ahead of it — the scenario must exercise
            # checkpoint recovery mid-stream
            epoch_seen = epoch_seen or any(
                record_kind(rec) == "epoch" and rec.get("worker") == w0_name
                and rec.get("t", 0.0) >= wall_submit
                for rec in board.poll())
            if (victims and epoch_seen
                    and all(1 <= seen[r] < max_new for r in victims)):
                migrated_expect = list(victims)
                fe.workers[victim_worker].kill()
                t_kill = time.perf_counter()
            time.sleep(0.002)

        assert t_kill is not None, "kill never fired (epoch never seen)"
        assert victims, "no stream was routed to the victim worker"
        assert migrated_expect, "every victim finished before the kill"
        outs = {r: fe.result(r) for r in rids}
        stats = dict(fe.stats)
        survivor_stats = fe.worker_stats()
        states = [fe.worker_state(i) for i in range(n_workers)]
        # read the dead worker's black box and the fleet registry BEFORE
        # gc — the victim's flight journal is exactly the kind of
        # dead-publisher object the sweep reclaims
        post = fe.postmortem(victim_worker, last=64)
        fleet_obs = fe.fleet_stats()
        gc = fe.gc_shared(ttl_s=0.0)
    finally:
        fe.stop()
    # the oracle runs in this process only once the workers are gone: on
    # a chip, a parent that touched JAX first would hold the device the
    # workers need
    ref = reference_tokens(cfg, prompts, max_new)

    # (a) token identity, migrated and surviving streams alike
    mismatches = [i for i, r in enumerate(rids) if outs[r] != ref[i]]
    assert not mismatches, (
        f"streams {mismatches} diverged from the uninterrupted run "
        f"(e.g. {outs[rids[mismatches[0]]]} vs {ref[mismatches[0]]})")

    # detector/recovery bookkeeping
    assert states[victim_worker] == "dead", states
    assert stats["workers_failed"] == 1, stats
    assert stats["streams_migrated"] == len(migrated_expect), stats
    assert stats["completed"] == n_streams + n_workers, stats

    survivors = [r for r in rids if r not in victims]
    pre_gaps = [g for r in rids
                for g in np.diff([t for t in arrivals[r] if t <= t_kill])]
    median_step_s = float(np.median(pre_gaps)) if pre_gaps else 0.0

    # (b) survivors keep emitting across the failure window
    surv_gaps = [g for r in survivors for g in _gaps(arrivals[r], t_kill)]
    assert surv_gaps, "survivor streams emitted nothing around the kill"
    p99_surv = quantile(surv_gaps, 0.99)
    surv_bound = HB_TIMEOUT_S + SURVIVOR_SLACK_S
    assert p99_surv <= surv_bound, (
        f"survivor p99 stall {p99_surv:.2f}s exceeds {surv_bound:.2f}s")

    # (c) migrated streams resume within the cadence-proportional bound
    rec_stalls = [_gaps(arrivals[r], t_kill)[0] for r in migrated_expect]
    recovery_stall = max(rec_stalls)
    rec_bound = (HB_TIMEOUT_S + CKPT_EVERY * median_step_s
                 + RECOVERY_SLACK_S)
    assert recovery_stall <= rec_bound, (
        f"recovery stall {recovery_stall:.2f}s exceeds "
        f"{rec_bound:.2f}s (cadence {CKPT_EVERY} steps x "
        f"{median_step_s * 1e3:.0f}ms)")

    # the survivors adopted board nodes after warmup — the migrated
    # prefixes' pages (epoch-published by the victim) ride the same
    # adoption path the ordinary prefix sharing uses
    adopted1 = [s["prefix"]["nodes_adopted"] for s in survivor_stats]
    adopted_delta = sum(adopted1) - sum(adopted0[1:])

    # the black box survived the SIGKILL: the victim's heartbeat-flushed
    # span timeline is post-mortem-readable from the shared domain (a
    # kill mid-append tears at most the final record — counted, dropped)
    assert post["records"], \
        "no flight records recovered for the killed worker"
    post_names = {r.get("name") for r in post["records"]}
    assert "step" in post_names, (
        f"victim's recovered timeline has no decode spans: "
        f"{sorted(post_names)}")

    return {
        "workers": n_workers,
        "streams": n_streams,
        "max_new": max_new,
        "victims": len(migrated_expect),
        "survivor_streams": len(survivors),
        "token_identity": True,
        "workers_failed": stats["workers_failed"],
        "streams_migrated": stats["streams_migrated"],
        "streams_completed_on_recovery":
            stats["streams_completed_on_recovery"],
        "worker_states": states,
        "median_step_s": median_step_s,
        "p99_stall_survivors": float(p99_surv),
        "survivor_stall_bound_s": surv_bound,
        "recovery_stall": float(recovery_stall),
        "recovery_stall_bound_s": rec_bound,
        "survivor_nodes_adopted_delta": int(adopted_delta),
        "shared_gc": gc,
        "postmortem": {
            "worker": post["worker"],
            "records_recovered": len(post["records"]),
            "torn_records": post["torn"],
            "span_names": sorted(n for n in post_names if n),
            # the dead worker's last seconds, verbatim — the operator's
            # view of what it was doing when the SIGKILL landed
            "timeline_tail": post["records"][-16:],
        },
        "_tier_stats": {f"elastic_survivor{i}": s["tier"]
                        for i, s in enumerate(survivor_stats)},
        "_registry": fleet_obs,
    }


def bench(smoke: bool) -> Dict:
    tmp = Path(tempfile.mkdtemp(prefix="deeper_fig13_"))
    m = measure_elastic(tmp,
                        n_workers=2 if smoke else 3,
                        n_streams=4 if smoke else 6,
                        max_new=MAX_NEW)
    tier_stats = m.pop("_tier_stats")
    registry = m.pop("_registry")
    return {
        "bench": "fig13_elastic_fleet",
        "arch": ARCH,
        "smoke": smoke,
        "page_tokens": PAGE_TOKENS,
        "max_len": MAX_LEN,
        "ckpt_every": CKPT_EVERY,
        "hb_interval_s": HB_INTERVAL_S,
        "hb_timeout_s": HB_TIMEOUT_S,
        "elastic": m,
        "_tier_stats": tier_stats,
        "_registry": registry,
    }


def _emit_json(res: Dict) -> Path:
    tier_stats = res.pop("_tier_stats")
    registry = res.pop("_registry", None)
    return bench_json("fig13_elastic_fleet", res, tier_stats=tier_stats,
                      registry=registry)


def run(smoke: bool = True):
    """Harness entry (benchmarks/run.py CSV contract)."""
    res = bench(smoke=smoke)
    _emit_json(res)
    m = res["elastic"]
    return [
        row("elastic_token_identity", 0.0,
            f"killed 1 of {m['workers']} workers; {m['streams_migrated']} "
            f"stream(s) migrated; CLAIM all {m['streams']} streams "
            "token-identical to the no-kill run: OK"),
        row("elastic_survivor_stall", m["p99_stall_survivors"] * 1e6,
            f"survivor p99 inter-token gap "
            f"{m['p99_stall_survivors'] * 1e3:.0f}ms; CLAIM <= "
            f"{m['survivor_stall_bound_s']:.2f}s: OK"),
        row("elastic_recovery_stall", m["recovery_stall"] * 1e6,
            f"migrated-stream gap {m['recovery_stall'] * 1e3:.0f}ms; "
            f"CLAIM <= hb_timeout + {res['ckpt_every']} steps x "
            f"{m['median_step_s'] * 1e3:.0f}ms + slack "
            f"= {m['recovery_stall_bound_s']:.2f}s: OK"),
        row("elastic_postmortem", 0.0,
            f"recovered {m['postmortem']['records_recovered']} flight "
            f"records from the killed worker "
            f"({m['postmortem']['torn_records']} torn); CLAIM decode "
            "timeline post-mortem-readable: OK"),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (2 workers, 4 streams)")
    args = ap.parse_args()
    res = bench(smoke=args.smoke)
    out_path = _emit_json(res)
    print(json.dumps(res, indent=1))
    m = res["elastic"]
    print(f"OK: killed 1/{m['workers']} workers mid-decode; "
          f"{m['streams_migrated']} streams migrated, all {m['streams']} "
          f"token-identical; survivor p99 stall "
          f"{m['p99_stall_survivors'] * 1e3:.0f}ms, recovery stall "
          f"{m['recovery_stall'] * 1e3:.0f}ms "
          f"(bound {m['recovery_stall_bound_s']:.2f}s); post-mortem "
          f"recovered {m['postmortem']['records_recovered']} flight "
          f"records from the victim -> {out_path}")


if __name__ == "__main__":
    main()
