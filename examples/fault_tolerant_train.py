"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
the full DEEP-ER resiliency stack (NAM-XOR checkpointing + failures).

Default arguments are sized for this CPU container (a ~20M model, 60
steps, ~5 min).  ``--hundred-m`` switches to a ~100M model and 200 steps
(the full exercise; budget ~1h on CPU, minutes on a real accelerator).

  PYTHONPATH=src python examples/fault_tolerant_train.py [--hundred-m]
"""

import argparse
import dataclasses
import tempfile
import time
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


def build_cfg(hundred_m: bool):
    base = get_config("phi3-mini-3.8b")
    if hundred_m:
        # ~100M params: 12L x 768 x 12H, 3072 FFN, 32k vocab
        return dataclasses.replace(
            base, n_layers=12, d_model=768, n_heads=12, n_kv_heads=12,
            head_dim=64, d_ff=3072, vocab_size=32064,
        )
    return dataclasses.replace(
        base, n_layers=6, d_model=384, n_heads=6, n_kv_heads=6,
        head_dim=64, d_ff=1536, vocab_size=8192,
    )


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    cfg = build_cfg(args.hundred_m)
    steps = args.steps or (200 if args.hundred_m else 60)

    model = get_model(cfg)
    n_params = cfg.param_count()
    print(f"model: {cfg.name} variant, ~{n_params/1e6:.0f}M params, {steps} steps")

    root = Path(tempfile.mkdtemp(prefix="deeper_ft_"))
    cluster = VirtualCluster(n_cluster=8, n_booster=4, root=root, xor_group_size=4)
    # TierStack router: BeeOND cache domain + NAM level + global tier,
    # composed by placement policy (memory/stack.py); NAM-XOR parity is
    # routed to the nam level via TierStack.offload
    stack = TierStack.for_cluster(cluster, with_nam=True)
    scr = SCRManager(cluster, stack, strategy=Strategy.NAM_XOR,
                     procs_per_node=2, keep=2, async_redundancy=True)
    pipeline = TokenPipeline(cfg.vocab_size, global_batch=8, seq_len=256)

    t0 = time.monotonic()
    with ResilienceSession(scr, policy=IntervalPolicy(20)) as session:
        trainer = Trainer(
            cfg, model, pipeline, session,
            opt_cfg=AdamWConfig(lr=6e-4, warmup_steps=20),
            failure_schedule=[
                FailureEvent(step=steps // 3, rank=5),
                FailureEvent(step=2 * steps // 3, rank=9),
            ],
        )
        report = trainer.run(total_steps=steps)
    wall = time.monotonic() - t0

    print(f"steps run            : {report.steps_run} in {wall:.0f}s")
    print(f"failures / recoveries: {report.failures} / {report.recoveries}")
    print(f"restarts from        : {report.restarts_from_step}")
    print(f"checkpoints          : {report.checkpoints} "
          f"(modelled fg {report.checkpoint_fg_s*1e3:.1f} ms total)")
    print(f"loss first -> last   : {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")
    assert report.recoveries == 2
    assert report.losses[-1] < report.losses[0]
    print("OK: two node losses survived via NAM-XOR parity reconstruction.")
    cluster.teardown()


if __name__ == "__main__":
    main()
