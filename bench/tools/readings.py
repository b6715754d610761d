"""Readings for the limits of `correct`: the program's numbers over many
seeds, the control's (the reference one matmul precision step below the
configuration's, in the program's place), and the program's with a
fault planted, all in one process.

    python bench/tools/readings.py --workload mag_mpnn.train \
        --seeds 11,12,13 --seconds 3 [--program] [--control] \
        [--fault half_batch]

Every number a driver can compare is read, also those the cell's limits
file leaves out; `correct` is the harness's own verdict over the cell's
limits.  Prints one JSON line per seed and reading.
"""
import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import device, faults, runner  # noqa: E402


# every number the drivers compute for a comparison
READ = ("loss_gap", "grad_gap", "head_grad_gap", "change_gap", "logit_gap")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args()
    w, cfg, traffic, limits, e2e, layers = runner.cell_spec(
        runner.read_benchmark(), args.workload)
    limits = {**dict.fromkeys(READ, None), **limits}
    devices = device.check(int(w["chips"]))
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.program or args.fault:
            planted = (faults.FAULTS[args.fault]() if args.fault
                       else contextlib.nullcontext())
            with planted:
                res = runner.run_cell(
                    args.workload, cfg, traffic, limits, seed=seed,
                    seconds=args.seconds, trace=False,
                    chips=int(w["chips"]), e2e=e2e, layers=layers)
            print(json.dumps({"seed": seed,
                              "reading": args.fault or "program",
                              "correct": res["correct"],
                              "checks": res["checks"],
                              "metrics": res["metrics"]}), flush=True)
        if args.control:
            ctx = runner.Ctx(args.workload, cfg, traffic, seed,
                             args.seconds, False, devices, 0.0, limits)
            checks = runner.control_checks(ctx)
            print(json.dumps({"seed": seed, "reading": "control",
                              "correct": checks.correct,
                              "checks": checks.as_dict()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
