"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``us_per_call`` is the measured
wall time of the functional stack on this container; ``derived`` carries
the paper-scale modelled numbers and the per-figure claim checks.

  PYTHONPATH=src python -m benchmarks.run [figN ...]
"""

from __future__ import annotations

import sys


def modules():
    from benchmarks import (
        fig3_nam_rma,
        fig4_nbody_strategies,
        fig5_sion,
        fig6_beeond_scaling,
        fig7_nvm_vs_hdd,
        fig8_scr_overhead,
        fig9_xor_vs_namxor,
        fig10_task_resilience,
        fig10_serve_throughput,
        fig11_prefix_reuse,
        fig12_fleet_scaling,
        fig13_elastic_fleet,
        roofline,
    )

    return {
        "fig3": fig3_nam_rma,
        "fig4": fig4_nbody_strategies,
        "fig5": fig5_sion,
        "fig6": fig6_beeond_scaling,
        "fig7": fig7_nvm_vs_hdd,
        "fig8": fig8_scr_overhead,
        "fig9": fig9_xor_vs_namxor,
        "fig10": fig10_task_resilience,
        "fig10serve": fig10_serve_throughput,
        "fig11prefix": fig11_prefix_reuse,
        "fig12fleet": fig12_fleet_scaling,
        "fig13elastic": fig13_elastic_fleet,
        "roofline": roofline,
    }


def main() -> None:
    from repro.launch.chip import enable_compile_cache

    enable_compile_cache()
    mods = modules()
    selected = sys.argv[1:] or list(mods)
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        mod = mods[name]
        try:
            for r in mod.run():
                derived = r["derived"].replace(",", ";")
                print(f"{r['name']},{r['us_per_call']},{derived}")
        except Exception as e:  # a failing figure should not hide the rest
            failures += 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
