import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
lowers, SPMD-partitions, and compiles — with per-device memory that fits
TPU v5e HBM — without any real hardware.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line above MUST precede any jax import (jax locks the device
count at first backend init).  Do not set this flag globally: smoke tests
and benchmarks expect 1 device.
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.distributed.sharding import param_shardings, use_sharding
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell
from repro.models.registry import runnable_cells

HBM_PER_CHIP = 16 * 1024 ** 3  # v5e

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(type_str: str) -> int:
    """bytes of an HLO type string like 'bf16[64,128,8,128]' (tuples summed)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in optimized HLO.

    Collectives inside while bodies are counted once per occurrence in the
    text; the roofline layer (launch/roofline.py) re-scales per-layer
    collectives by the scan trip count analytically.  We also return the
    per-op breakdown so the schedule is inspectable.
    """
    per_op = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    ops = []
    for line in hlo_text.splitlines():
        s = line.strip()
        # e.g.  %all-reduce.5 = bf16[1024,512]{1,0} all-reduce(...)
        m = re.match(r"%?([\w.-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+([a-z-]+)", s)
        if not m:
            continue
        opname = m.group(3)
        if opname.rstrip("-start").rstrip("-done") in COLLECTIVE_OPS:
            base = opname
            for suffix in ("-start", "-done"):
                if base.endswith(suffix):
                    base = base[: -len(suffix)]
            if base not in COLLECTIVE_OPS or opname.endswith("-done"):
                continue
            nbytes = _shape_bytes(m.group(2))
            per_op[base]["count"] += 1
            per_op[base]["bytes"] += nbytes
            ops.append({"op": base, "bytes": nbytes, "name": m.group(1)})
    total = sum(v["bytes"] for v in per_op.values())
    return {"total_bytes": total, "per_op": per_op, "n_ops": len(ops)}


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    cell = make_cell(arch, shape)
    overrides = cell.rule_overrides
    with use_sharding(mesh, param_rules=overrides, act_rules=overrides):
        arg_shardings = []
        for i, (spec, ax) in enumerate(zip(cell.args, cell.arg_axes)):
            kind = "param" if (i == 0 or (cell.kind == "train" and i == 1)) \
                else "act"
            arg_shardings.append(
                param_shardings(ax, kind=kind, specs_tree=spec))
        jitted = jax.jit(cell.fn, in_shardings=tuple(arg_shardings),
                         donate_argnums=cell.donate)
        with mesh:
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    result = {
        "arch": arch, "shape": shape, "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "status": "OK",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "argument": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "hbm_fit": None,
        "hlo_flops_per_device": float(cost.get("flops", 0.0)) if cost else 0.0,
        "hlo_bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        "collectives": coll,
    }
    # Donation adjustment: the CPU compile pipeline does not implement
    # buffer donation, so donated inputs (params/opt-state for train, the KV
    # cache for decode) are double-counted — once as argument and once as
    # output copy.  On the TPU target the output aliases the donated input.
    b = result["bytes_per_device"]
    donated = 0
    for i in cell.donate:
        donated += sum(
            int(s.size * s.dtype.itemsize)
            for s in jax.tree_util.tree_leaves(cell.args[i])) // n_chips
    overlap = min(donated, b["output"])
    result["donated_bytes_per_device"] = donated
    live = b["argument"] + b["temp"] + b["output"] - overlap
    result["live_bytes_per_device"] = live
    result["hbm_fit"] = bool(live <= HBM_PER_CHIP)
    if verbose:
        print(f"[{result['mesh']}] {arch} × {shape} ({cell.kind}): "
              f"compile {t_compile:.0f}s, "
              f"live/device {live/2**30:.2f} GiB "
              f"(fit={result['hbm_fit']}), "
              f"collectives {coll['total_bytes']/2**20:.1f} MiB "
              f"in {coll['n_ops']} ops", flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell on both meshes")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args(argv)

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())

    def done(arch, shape, mesh):
        return any(r["arch"] == arch and r["shape"] == shape
                   and r["mesh"] == mesh and r["status"] == "OK"
                   for r in results)

    cells = ([(args.arch, args.shape, args.multi_pod)]
             if not args.all else
             [(a, s, mp) for (a, s) in runnable_cells()
              for mp in (False, True)])

    failures = 0
    for arch, shape, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        if args.all and done(arch, shape, mesh_name):
            print(f"skip cached {arch} × {shape} [{mesh_name}]", flush=True)
            continue
        try:
            r = run_cell(arch, shape, multi_pod=mp)
        except Exception as e:  # noqa: BLE001 — record and continue
            traceback.print_exc()
            r = {"arch": arch, "shape": shape,
                 "mesh": mesh_name, "status": f"FAIL: {e}"}
            failures += 1
        results = [x for x in results
                   if not (x["arch"] == arch and x["shape"] == shape
                           and x["mesh"] == r["mesh"])]
        results.append(r)
        out_path.write_text(json.dumps(results, indent=1))
    print(f"dry-run complete: {len(results)} results, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
