import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step for train
shapes, prefill/serve_step for inference shapes), lowers it against
ShapeDtypeStruct stand-ins with the production shardings, compiles it,
and extracts the roofline inputs:

  * memory_analysis()  — per-device bytes (proves the cell fits); train
    cells are lowered with gradient accumulation (micro_batch=2 per
    device) exactly as the trainer runs them,
  * cost_analysis()    — HLO FLOPs / bytes.  XLA counts a while-loop body
    ONCE, so every cell is lowered at scan_unroll=1 and scan_unroll=2 and
    the diff isolates the per-layer body cost; totals are reconstructed
    as F1 + (trips-1)*(F2-F1) (zamba2's two-level scan uses a third
    lowering, see _hybrid_adjust).  Chunked-scan kernels nested *inside*
    a layer (flash attention, WKV6, SSD) are likewise once-counted; their
    true cost is added analytically (formulas in _analytic_corrections,
    documented in EXPERIMENTS.md §Roofline methodology),
  * the collective schedule — parsed from the SPMD-partitioned HLO with
    ring-algorithm byte accounting per device:
      all-reduce 2*S*(g-1)/g | all-gather S*(g-1)/g | reduce-scatter
      S_out*(g-1) | all-to-all S*(g-1)/g | collective-permute S.

Results append incrementally to JSON; interrupted sweeps resume.

Usage:
  python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out FILE]
"""

import argparse
import dataclasses
import json
import math
import re
import time
import traceback
from pathlib import Path

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, get_config, get_shape, shapes_for
from repro.configs.shapes import ShapeSpec
from repro.distributed.sharding import (
    DECODE_RULES,
    TRAIN_RULES,
    fit_spec,
    shardings_for,
    shardings_for_shapes,
)
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models.registry import get_model, input_specs
from repro.train.step import (
    make_prefill_step,
    make_serve_step,
    make_train_step,
    train_state_axes,
    train_state_shapes,
)

# ---------------------------------------------------------------------- #
# collective parsing (SPMD-partitioned HLO, per-device shapes)
# ---------------------------------------------------------------------- #

OP_RE = re.compile(
    r"= (?P<rtype>.*?) (?P<kind>all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\("
)
SHAPE_RE = re.compile(r"\b((?:f|bf|s|u|c)[0-9]{1,2}|pred)\[([0-9,]*)\]")
GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        size = 1
        if dims:
            for d in dims.split(","):
                size *= int(d)
        total += size * DTYPE_BYTES.get(dt, 4)
    return total


def _group_size(line: str) -> int:
    m = GROUPS_RE.search(line)
    if m:
        return max(1, int(m.group(2)))
    m = GROUPS_BRACE_RE.search(line)
    if m:
        return max(1, len(m.group(1).split(",")))
    return 2  # collective-permute / unknown: neutral default


def parse_collectives(hlo_text: str):
    """Ring-model per-device bytes moved, per collective kind."""
    out = {}
    for line in hlo_text.splitlines():
        m = OP_RE.search(line)
        if m is None:
            continue
        kind = m.group("kind")
        s = _shape_bytes(m.group("rtype"))  # result shape(s), per device
        g = _group_size(line)
        if kind == "all-reduce":
            moved = 2.0 * s * (g - 1) / g
        elif kind == "all-gather":
            moved = s * (g - 1) / g
        elif kind == "reduce-scatter":
            moved = float(s) * (g - 1)
        elif kind == "all-to-all":
            moved = s * (g - 1) / g
        else:  # collective-permute
            moved = float(s)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += moved
    return out


def _coll_diff(c2, c1, factor):
    """c1 + factor*(c2-c1) per kind; clamps at >=0."""
    out = {}
    kinds = set(c1) | set(c2)
    for k in kinds:
        a = c1.get(k, {"count": 0, "bytes": 0.0})
        b = c2.get(k, {"count": 0, "bytes": 0.0})
        out[k] = {
            "count": int(max(0, a["count"] + factor * (b["count"] - a["count"]))),
            "bytes": float(max(0.0, a["bytes"] + factor * (b["bytes"] - a["bytes"]))),
        }
    return out


def _coll_add(c1, c2, w2=1.0):
    out = {k: dict(v) for k, v in c1.items()}
    for k, v in c2.items():
        rec = out.setdefault(k, {"count": 0, "bytes": 0.0})
        rec["count"] += int(w2 * v["count"])
        rec["bytes"] += w2 * v["bytes"]
    return out


# ---------------------------------------------------------------------- #
# analytic corrections for once-counted nested-scan kernels
# ---------------------------------------------------------------------- #


def _analytic_corrections(cfg, shape: ShapeSpec, n_dp: int, tp: int):
    """Per-DEVICE (flops, bytes) of the chunked kernels that XLA's cost
    analysis sees only once (they live in scans nested inside the layer
    scan).  train multiplies by 4 (fwd + remat recompute + ~2x bwd)."""
    if shape.kind == "decode":
        return 0.0, 0.0  # decode kernels are plain ops in the layer body
    mult = 4.0 if shape.kind == "train" else 1.0
    b = shape.global_batch / n_dp
    t = shape.seq_len
    flops = 0.0
    byts = 0.0
    cd_bytes = 2  # bf16 compute

    def attn(tq, tk, h_padded, d_qk, d_v, layers):
        h = h_padded / tp
        f = 2.0 * b * h * tq * tk * (d_qk + d_v) * layers
        # flash streams K/V once per q chunk (q_chunk=2048 in layers.py)
        nq = max(1, math.ceil(tq / 2048))
        by = b * h * layers * (
            nq * tk * (d_qk + d_v) + tq * (d_qk + d_v)
        ) * cd_bytes
        return f, by

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        if cfg.mla is not None:
            d_qk = cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim
            d_v = cfg.mla.v_head_dim
        else:
            d_qk = d_v = cfg.resolved_head_dim
        tq = t
        f, by = attn(tq, tq, cfg.padded_heads, d_qk, d_v, cfg.n_layers)
        flops += f
        byts += by
    elif fam == "encdec":
        dh = cfg.resolved_head_dim
        f1, b1 = attn(cfg.enc_seq, cfg.enc_seq, cfg.padded_heads, dh, dh,
                      cfg.n_enc_layers)
        f2, b2 = attn(t, t, cfg.padded_heads, dh, dh, cfg.n_layers)
        f3, b3 = attn(t, cfg.enc_seq, cfg.padded_heads, dh, dh, cfg.n_layers)
        flops += f1 + f2 + f3
        byts += b1 + b2 + b3
    elif fam == "hybrid":
        dh = cfg.resolved_head_dim
        n_shared = cfg.n_layers // cfg.attn_every
        f, by = attn(t, t, cfg.padded_heads, dh, dh, n_shared)
        flops += f
        byts += by
        # SSD chunked scan (ops.mamba2_chunked: chunk=64)
        c, n, p = 64, cfg.ssm_state, cfg.ssm_state
        h = cfg.padded_ssm_heads / tp
        nc = math.ceil(t / c)
        per_chunk = 2.0 * c * c * n + 2.0 * c * c * h * p + 4.0 * c * h * n * p
        flops += b * nc * per_chunk * cfg.n_layers
        byts += b * t * h * (p + 2 * n / max(h, 1)) * 4 * cfg.n_layers
    elif fam == "rwkv":
        c, d = 32, cfg.ssm_state  # ops.wkv6_chunked defaults
        h = cfg.padded_rwkv_heads / tp
        nc = math.ceil(t / c)
        per_chunk = 6.0 * c * c * d + 4.0 * c * d * d
        flops += b * h * nc * per_chunk * cfg.n_layers
        byts += b * t * h * d * 4 * 4 * cfg.n_layers
    return flops * mult, byts * mult


def model_flops(cfg, shape: ShapeSpec) -> float:
    n = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch


# ---------------------------------------------------------------------- #
# lowering
# ---------------------------------------------------------------------- #


def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_shardings(specs, mesh):
    dp = _dp_axes(mesh)
    return jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(dp, *([None] * (len(s.shape) - 1)))
        ),
        specs,
    )


def _lower_one(cfg, shape, mesh, micro_batches=1):
    """Lower + compile one step function; returns compiled object."""
    model = get_model(cfg)
    rules = TRAIN_RULES if shape.kind != "decode" else DECODE_RULES
    with mesh:
        if shape.kind == "train":
            step = make_train_step(cfg, model, mesh=mesh, remat=True,
                                   micro_batches=micro_batches)
            state_shapes = train_state_shapes(cfg, model)
            state_shardings = shardings_for(train_state_axes(cfg, model), rules, mesh)
            batch_specs = input_specs(cfg, shape)
            bs = _batch_shardings(batch_specs, mesh)
            lowered = jax.jit(
                step,
                in_shardings=(state_shardings, bs),
                out_shardings=(state_shardings, None),
                donate_argnums=(0,),
            ).lower(state_shapes, batch_specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, model, mesh=mesh)
            p_shardings = shardings_for(model.param_axes(cfg), rules, mesh)
            batch_specs = input_specs(cfg, shape)
            bs = _batch_shardings(batch_specs, mesh)
            lowered = jax.jit(step, in_shardings=(p_shardings, bs)).lower(
                model.param_shapes(cfg), batch_specs
            )
        else:
            step = make_serve_step(cfg, model)
            p_shardings = shardings_for(model.param_axes(cfg), rules, mesh)
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(cfg, shape.global_batch, shape.seq_len)
            )
            cache_shardings = shardings_for_shapes(
                model.cache_axes(cfg), cache_shapes, rules, mesh
            )
            tok_specs = input_specs(cfg, shape)["tokens"]
            tok_sharding = jax.sharding.NamedSharding(
                mesh,
                fit_spec(jax.sharding.PartitionSpec(_dp_axes(mesh)),
                         tok_specs.shape, mesh),
            )
            lowered = jax.jit(
                step,
                in_shardings=(p_shardings, cache_shardings, tok_sharding, None),
                out_shardings=(tok_sharding, cache_shardings),
                donate_argnums=(1,),
            ).lower(
                model.param_shapes(cfg), cache_shapes, tok_specs,
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        return lowered.compile()


def _metrics(compiled):
    cost = compiled.cost_analysis() or {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": parse_collectives(compiled.as_text()),
    }


def _memory(compiled):
    mem = compiled.memory_analysis()
    rec = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            if hasattr(mem, attr):
                rec[attr] = int(getattr(mem, attr))
    return rec


def _scan_trips(cfg, shape) -> int:
    """Trip count of the layer scan(s) unrolled by cfg.scan_unroll."""
    if cfg.family == "moe":
        return cfg.n_layers - cfg.moe.n_dense_layers
    if cfg.family == "encdec":
        return cfg.n_layers  # enc & dec scans share the trip count (4)
    return cfg.n_layers


def apply_variant(cfg, variant: Optional[Dict] = None):
    """Apply §Perf optimization flags to a config.

    Recognized keys: precast_params, seq_parallel, fused_gate_up (bools),
    capacity_factor (float, MoE).
    """
    if not variant:
        return cfg
    kw = dict(variant)
    cf = kw.pop("capacity_factor", None)
    kw = {k: v for k, v in kw.items()}
    if cf is not None and cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, capacity_factor=float(cf))
    return dataclasses.replace(cfg, **kw)


def lower_cell(arch: str, shape_name: str, mesh, tp: int,
               fast: bool = False, variant: Optional[Dict] = None):
    """Full instrumented lowering of one (arch x shape x mesh) cell.

    fast=True compiles only the base lowering (multi-pod pass/fail mode).
    variant applies §Perf optimization flags (see apply_variant).
    """
    base_cfg = apply_variant(get_config(arch), variant)
    shape = get_shape(shape_name)
    n_dp = 1
    for a, sz in zip(mesh.axis_names, mesh.devices.shape):
        if a in ("pod", "data"):
            n_dp *= sz

    micro = 1
    if shape.kind == "train":
        per_dev = shape.global_batch // n_dp
        micro = max(1, per_dev // 2)  # micro-batch of 2 sequences/device

    timings = {}
    t0 = time.monotonic()
    cfg1 = base_cfg.with_tp(tp)
    c_mem = _lower_one(cfg1, shape, mesh,
                       micro_batches=micro if shape.kind == "train" else 1)
    timings["base_compile_s"] = round(time.monotonic() - t0, 1)
    mem = _memory(c_mem)
    if shape.kind == "train" and micro > 1:
        # cost metrics come from the no-micro lowering (one fwd+bwd over
        # the full per-device batch; grad psums identical)
        del c_mem
        t0 = time.monotonic()
        c1 = _lower_one(cfg1, shape, mesh, micro_batches=1)
        timings["u1_compile_s"] = round(time.monotonic() - t0, 1)
    else:
        c1 = c_mem
    m1 = _metrics(c1)
    del c1

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "tp": tp,
        "ok": True,
        "variant": dict(variant or {}),
        "micro_batches": micro,
        "memory": mem,
        "hlo_flops_raw": m1["flops"],
        "hlo_bytes_raw": m1["bytes"],
        "collectives_raw": m1["coll"],
        "model_flops": model_flops(base_cfg, shape),
        "timings": timings,
    }
    if fast:
        record["adjusted"] = False
        return record

    # --- unroll-diff trip adjustment -------------------------------------
    t0 = time.monotonic()
    cfg2 = dataclasses.replace(cfg1, scan_unroll=2)
    c2 = _lower_one(cfg2, shape, mesh, micro_batches=1)
    timings["u2_compile_s"] = round(time.monotonic() - t0, 1)
    m2 = _metrics(c2)
    del c2

    if base_cfg.family == "hybrid":
        t0 = time.monotonic()
        cfg3 = dataclasses.replace(cfg1, group_unroll=2)
        c3 = _lower_one(cfg3, shape, mesh, micro_batches=1)
        timings["g2_compile_s"] = round(time.monotonic() - t0, 1)
        m3 = _metrics(c3)
        del c3
        groups = base_cfg.n_layers // base_cfg.attn_every
        per = base_cfg.attn_every
        # total = F1 + (groups*per - per)*(F2-F1) + (groups-1)*(F3-F1)
        fac_a = groups * per - per
        fac_b = groups - 1
        flops = m1["flops"] + fac_a * (m2["flops"] - m1["flops"]) \
            + fac_b * (m3["flops"] - m1["flops"])
        byts = m1["bytes"] + fac_a * (m2["bytes"] - m1["bytes"]) \
            + fac_b * (m3["bytes"] - m1["bytes"])
        coll = {}
        for kinds in (m1["coll"], m2["coll"], m3["coll"]):
            for k in kinds:
                coll.setdefault(k, {"count": 0, "bytes": 0.0})
        for k in coll:
            a = m1["coll"].get(k, {"count": 0, "bytes": 0.0})
            b2_ = m2["coll"].get(k, {"count": 0, "bytes": 0.0})
            b3_ = m3["coll"].get(k, {"count": 0, "bytes": 0.0})
            coll[k]["count"] = int(a["count"] + fac_a * (b2_["count"] - a["count"])
                                   + fac_b * (b3_["count"] - a["count"]))
            coll[k]["bytes"] = float(a["bytes"] + fac_a * (b2_["bytes"] - a["bytes"])
                                     + fac_b * (b3_["bytes"] - a["bytes"]))
    else:
        trips = _scan_trips(base_cfg, shape)
        fac = trips - 1
        flops = m1["flops"] + fac * (m2["flops"] - m1["flops"])
        byts = m1["bytes"] + fac * (m2["bytes"] - m1["bytes"])
        coll = _coll_diff(m2["coll"], m1["coll"], float(fac))

    corr_f, corr_b = _analytic_corrections(base_cfg.with_tp(tp), shape, n_dp, tp)
    record.update({
        "adjusted": True,
        "hlo_flops": float(flops + corr_f),
        "hlo_bytes": float(byts + corr_b),
        "kernel_corr_flops": corr_f,
        "kernel_corr_bytes": corr_b,
        "collectives": coll,
        "timings": timings,
    })
    return record


# ---------------------------------------------------------------------- #
# sweep driver
# ---------------------------------------------------------------------- #


def run_cells(cells, multi_pod: bool, out_path: Path, test_mesh: bool = False,
              fast: bool = False, variant: Optional[Dict] = None):
    mesh = (make_test_mesh if test_mesh else make_production_mesh)(multi_pod=multi_pod)
    tp = mesh.devices.shape[-1]
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())
    vkey = json.dumps(variant or {}, sort_keys=True)
    done = {(r["arch"], r["shape"], r["mesh"], json.dumps(r.get("variant", {}), sort_keys=True))
            for r in results if r.get("ok")}
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    for arch, shape_name in cells:
        if (arch, shape_name, mesh_name, vkey) in done:
            print(f"[skip] {arch} {shape_name} {mesh_name} (cached)", flush=True)
            continue
        print(f"[dryrun] {arch} x {shape_name} on {mesh_name} ...", flush=True)
        t0 = time.monotonic()
        try:
            record = lower_cell(arch, shape_name, mesh, tp, fast=fast,
                                variant=variant)
            coll = record.get("collectives", record.get("collectives_raw", {}))
            print(f"  ok in {time.monotonic()-t0:.0f}s: "
                  f"flops/dev {record.get('hlo_flops', record['hlo_flops_raw']):.3e} "
                  f"coll_bytes/dev {sum(v['bytes'] for v in coll.values()):.3e}",
                  flush=True)
        except Exception as e:
            record = {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "ok": False, "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:],
            }
            print(f"  FAIL: {record['error']}", flush=True)
        results = [r for r in results
                   if not (r["arch"] == arch and r["shape"] == shape_name
                           and r["mesh"] == mesh_name
                           and json.dumps(r.get("variant", {}), sort_keys=True) == vkey)]
        results.append(record)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(results, indent=1))
    return results


def all_cells():
    cells = []
    for arch, cfg in sorted(REGISTRY.items()):
        for shape in shapes_for(cfg.family):
            cells.append((arch, shape.name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="base lowering only (pass/fail + memory)")
    ap.add_argument("--test-mesh", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--set", action="append", default=[],
                    help="variant flag key=value (e.g. precast_params=1)")
    args = ap.parse_args()
    variant = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        variant[k] = float(v) if k == "capacity_factor" else bool(int(v))

    cells = all_cells() if args.all else None
    if cells is None:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]
    out = Path(args.out)
    results = run_cells(cells, args.multi_pod, out, test_mesh=args.test_mesh,
                        fast=args.fast or args.multi_pod, variant=variant)
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells OK -> {out}")


if __name__ == "__main__":
    main()
