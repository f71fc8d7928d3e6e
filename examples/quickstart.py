"""Quickstart: train a small LM with DEEP-ER multi-level checkpointing.

Runs in ~1 minute on CPU.  Demonstrates:
  * the Cluster-Booster virtual topology (4+4 nodes),
  * the SCR-style session API (ResilienceSession: need/start/route/
    complete checkpoint transactions over a pluggable policy),
  * BUDDY checkpointing (SIONlib-aggregated containers on the partner),
  * the asynchronous BeeOND->global drain (training overlaps the flush),
  * a node failure mid-run, fragment reconstruction, and resume.

  PYTHONPATH=src python examples/quickstart.py
"""

import tempfile
from pathlib import Path

from repro.api import IntervalPolicy, ResilienceSession
from repro.cluster.topology import VirtualCluster
from repro.configs import get_config
from repro.core.scr import SCRManager, Strategy
from repro.data.pipeline import TokenPipeline
from repro.launch.chip import enable_compile_cache
from repro.memory.stack import TierStack
from repro.models.registry import get_model
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import FailureEvent, Trainer


def main():
    enable_compile_cache()
    cfg = get_config("phi3-mini-3.8b").reduced()
    model = get_model(cfg)
    root = Path(tempfile.mkdtemp(prefix="deeper_quickstart_"))

    cluster = VirtualCluster(n_cluster=4, n_booster=4, root=root)
    # BeeOND cache domain + global tier composed by the TierStack router;
    # SCR drains checkpoints through the cache domain to global storage.
    # The session is the user surface: transactional checkpoints, policy-
    # driven cadence, context-managed shutdown (no leaked drain threads).
    stack = TierStack.for_cluster(cluster)
    scr = SCRManager(cluster, stack, strategy=Strategy.BUDDY,
                     procs_per_node=2, async_drain=True)
    pipeline = TokenPipeline(cfg.vocab_size, global_batch=8, seq_len=128)

    with ResilienceSession(scr, policy=IntervalPolicy(10)) as session:
        trainer = Trainer(
            cfg, model, pipeline, session,
            opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=10),
            failure_schedule=[FailureEvent(step=17, rank=3)],  # kill node 3
        )
        report = trainer.run(total_steps=30)

    print(f"steps run           : {report.steps_run}")
    print(f"node failures       : {report.failures}")
    print(f"recoveries          : {report.recoveries} "
          f"(restarted from step {report.restarts_from_step})")
    print(f"checkpoints written : {report.checkpoints} "
          f"({report.drains_completed} drained in background)")
    print(f"loss first -> last  : {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")
    assert report.recoveries == 1 and report.losses[-1] < report.losses[0]
    print("OK: failure survived, training resumed from the buddy copy.")
    cluster.teardown()


if __name__ == "__main__":
    main()
