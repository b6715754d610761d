"""One run of one cell: device check, the cell's driver, its metrics and
the contract's last line.

A cell (BENCHMARK.json ``workloads``) names a configuration and a traffic
mix.  The configuration is ``bench/configs/<config>.json``; the traffic
mix is ``bench/traffic/<traffic>.json`` and names its driver,
``bench/drivers/<driver>.py``; each per-layer metric is a reader,
``bench/metrics/<metric>.py``.  All are found by name, so a new cell,
mix or metric is new files and entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

from bench.harness import dataset, device, trace
from bench.harness.program import BENCH, load_module

ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Ctx:
    """What a driver gets."""
    cell: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    t0: float                        # process start, host clock
    limits: dict                     # comparison name -> limit
    cache_dir: Path = dataset.CACHE_DIR
    setup: dict = dataclasses.field(default_factory=dict)
    host_trace: bool = False         # a trace's host ops stand in for a chip

    def mark(self, phase: str, since: float) -> float:
        """Adds the seconds since `since` to the set-up split."""
        now = time.perf_counter()
        self.setup[phase] = self.setup.get(phase, 0.0) + now - since
        return now

    def trace_dir(self) -> str:
        return tempfile.mkdtemp(prefix="bench-trace-")

    def reduce_trace(self, log_dir: str):
        """The trace under `log_dir`, reduced (trace.reduce_trace)."""
        return trace.reduce_trace(trace.find_xplane(log_dir),
                                  host_ops=self.host_trace)


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    e2e: dict                        # end-to-end metric -> value
    records: dict                    # host counts for the metric readers
    checks: object                   # check.Checks
    attempted: int
    failed: int
    memory_peak_bytes: int
    reduced: Optional[object] = None  # trace.Reduced of a --trace 1 run


def read_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(bench: dict, cell: str) -> tuple:
    """(workload entry, configuration, traffic, limits, end-to-end metric
    names, per-layer metric names) of a cell of BENCHMARK.json.  The
    limits of the cell's comparisons are ``bench/limits/<cell>.json``."""
    work = {w["name"]: w for w in bench["workloads"]}
    if cell not in work:
        raise SystemExit(f"no workload {cell!r} in BENCHMARK.json")
    w = work[cell]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / conf["file"]) as f:
        cfg = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(BENCH / "limits" / f"{cell}.json") as f:
        limits = json.load(f)

    def mine(m):
        return cell in m.get("workloads", [cell])
    e2e = [m["name"] for m in bench["end_to_end"] if mine(m)]
    layers = [m["name"] for m in bench["per_layer"] if mine(m)]
    return w, cfg, traffic, limits, e2e, layers


def process_start() -> float:
    """This process's start on the `time.perf_counter` clock."""
    import psutil
    age = time.time() - psutil.Process().create_time()
    return time.perf_counter() - age


def run_cell(cell: str, cfg: dict, traffic: dict, limits: dict, *,
             seed: int,
             seconds: float, trace: bool, chips: int, e2e: list,
             layers: list, devices_fn: Callable = device.check,
             peaks_fn: Callable = device.peaks,
             cache_dir: Path = dataset.CACHE_DIR,
             t0: Optional[float] = None, host_trace: bool = False) -> dict:
    """Runs the cell and returns the result line (a dict).  The tests
    skip the chip by `devices_fn` and `peaks_fn`, and read a CPU trace
    by `host_trace`."""
    t0 = process_start() if t0 is None else t0
    devices = devices_fn(chips)
    dev = device.describe(devices)
    peaks = peaks_fn(dev["kind"])
    from repro.runtime import enable_compile_cache
    log(f"device {dev}; compile cache {enable_compile_cache()}")
    ctx = Ctx(cell, cfg, traffic, int(seed), float(seconds), bool(trace),
              devices, t0, limits, Path(cache_dir), host_trace=host_trace)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    out = driver.run(ctx)
    dev["memory_peak_bytes"] = out.memory_peak_bytes
    result = {"correct": out.checks.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": {}, "device": dev}
    if not trace:
        units = {"setup_s": "s"}
        units.update(out.records.get("units", {}))
        for name in e2e:
            result["metrics"][name] = {"value": out.e2e[name],
                                       "unit": units[name]}
    else:
        red = out.reduced
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        run = {"records": out.records, "trace": red, "peaks": peaks,
               "chips": len(devices), "cfg": cfg}
        for name in layers:
            reader = load_module(BENCH / "metrics" / f"{name}.py")
            value = reader.read(run)
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": reader.UNIT}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops],
            "idle_gaps": [[n, s] for n, s in red.gaps]}
    log("set-up split (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in ctx.setup.items()))
    for line in out.checks.lines():
        log(line)
    result["checks"] = out.checks.as_dict()
    return result


def control_checks(ctx: Ctx):
    """The control (the driver's ``control``: the reference one matmul
    precision step below the configuration's, in the program's place),
    judged by the harness's own comparison over `ctx.limits`."""
    from bench.harness import check
    driver = load_module(BENCH / "drivers" / f"{ctx.traffic['driver']}.py")
    checks = check.Checks(ctx.limits)
    for name, value in driver.control(ctx).items():
        if name in ctx.limits:
            checks.add(name, value)
    return checks


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = process_start()
    bench = read_benchmark()
    w, cfg, traffic, limits, e2e, layers = cell_spec(bench, args.workload)
    try:
        result = run_cell(args.workload, cfg, traffic, limits,
                          seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          chips=int(w["chips"]), e2e=e2e, layers=layers,
                          t0=t0)
    except device.NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
