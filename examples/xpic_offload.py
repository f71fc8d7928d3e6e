"""xPic-style Cluster-Booster offload with OmpSs-style task resiliency.

A miniature particle-in-cell (PIC) simulation split exactly like the
paper's xPic (§IV): the FIELD solver runs on the Cluster module, the
PARTICLE solver is offloaded to the Booster module; the two exchange
moments/fields every step over the "fabric" (mesh sub-grids).  The
offloaded particle tasks run under the resilient task runtime: an
injected Booster-rank failure restarts only that task from its input
snapshot — no global rollback (the paper's OmpSs resilient-offload
result, Fig 10).

  PYTHONPATH=src python examples/xpic_offload.py
"""

import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.topology import Module, NodeState, VirtualCluster
from repro.core.offload import OffloadEngine, split_mesh
from repro.core.tasks import TaskRuntime
from repro.launch.chip import enable_compile_cache
from repro.memory.tiers import MemoryHierarchy

GRID = 64          # field grid cells
N_PART = 4096      # particles
DT = 0.1


def field_solve(e_field, current):
    """Cluster side: update E field from deposited current (toy Maxwell)."""
    lap = jnp.roll(e_field, 1) - 2 * e_field + jnp.roll(e_field, -1)
    return e_field + DT * (0.5 * lap - current)


def particle_push(pos, vel, e_field):
    """Booster side: push particles in the interpolated field, deposit
    current (toy moment gathering)."""
    cell = (pos * GRID).astype(jnp.int32) % GRID
    e_at_p = e_field[cell]
    vel = vel + DT * e_at_p
    pos = (pos + DT * vel) % 1.0
    current = jnp.zeros((GRID,)).at[cell].add(vel) / (N_PART / GRID)
    return pos, vel, current


def main():
    enable_compile_cache()
    # Cluster-Booster split of the device grid (1 CPU device here, but the
    # same split works on any mesh — see tests/test_offload.py on 8 devs)
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    from jax.sharding import Mesh
    mesh = Mesh(devs, ("data", "model"))
    cluster = VirtualCluster(n_cluster=2, n_booster=2,
                             root=Path(tempfile.mkdtemp(prefix="xpic_")))
    # task journal only needs the durable global tier, no stack
    hierarchy = MemoryHierarchy(cluster)
    runtime = TaskRuntime(cluster, journal_tier=hierarchy.global_tier,
                          max_retries=3)

    key = jax.random.PRNGKey(0)
    pos = jax.random.uniform(key, (N_PART,))
    vel = jnp.zeros((N_PART,))
    e_field = jnp.sin(jnp.linspace(0, 6.28, GRID))
    current = jnp.zeros((GRID,))

    booster_rank = cluster.ranks(Module.BOOSTER)[0]
    cluster.arm_failure(booster_rank, NodeState.FAILED_TRANSIENT)  # fires in step 3

    energy = []
    for step in range(8):
        # field solve on the Cluster module
        e_field = runtime.run(
            f"field_{step}", field_solve, e_field, current,
            rank=cluster.ranks(Module.CLUSTER)[0], persistent=True,
        )
        # particle push OFFLOADED to the Booster module; step 3 hits the
        # armed failure, the runtime snapshots inputs + retries on recovery
        if step == 3:
            cluster.arm_failure(booster_rank, NodeState.FAILED_TRANSIENT)
        pos, vel, current = runtime.run(
            f"particles_{step}", particle_push, pos, vel, e_field,
            rank=booster_rank, persistent=True,
        )
        energy.append(float(jnp.sum(vel**2) + jnp.sum(e_field**2)))

    s = runtime.stats
    print(f"steps completed      : 8")
    print(f"tasks launched       : {s.launched} (retried {s.retried}, "
          f"replayed {s.replayed}, failed {s.failed})")
    print(f"field energy t0 -> t7: {energy[0]:.3f} -> {energy[-1]:.3f}")
    assert s.retried >= 1 and s.failed == 0
    print("OK: offloaded particle task survived a Booster failure without "
          "global rollback.")

    # fast-forward replay: a fresh runtime (post-crash) skips journaled tasks
    runtime2 = TaskRuntime(cluster, journal_tier=hierarchy.global_tier)
    e2 = runtime2.run("field_0", field_solve, None, None,
                      rank=cluster.ranks(Module.CLUSTER)[0], persistent=True)
    assert runtime2.stats.replayed == 1
    print("OK: persistent journal fast-forwards recomputation after a crash.")
    cluster.teardown()


if __name__ == "__main__":
    main()
