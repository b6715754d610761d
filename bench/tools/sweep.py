"""Finds the serving knee: one warmed `GNNServer`, open-loop windows at
rising rates, each reported with its p95 and the rate it answered.

    python bench/tools/sweep.py --config mag_mpnn --rates 50,100,200 \
        --seconds 8 --seed 1

Run once on the chip when a serving cell is defined; the cell's traffic
file then fixes its rate as a number.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.harness import dataset, device, loadgen  # noqa: E402
from bench.harness.program import BENCH, Program  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    device.check(1)
    from repro.runtime import enable_compile_cache
    from repro.serve import GNNServer
    enable_compile_cache()
    cfg = json.load(open(BENCH / "configs" / f"{args.config}.json"))
    store, _ = dataset.load_store(cfg["dataset"])
    prog = Program(cfg, store)
    weights = prog.make_weights(args.seed)
    s = cfg["serve"]
    n = store.num_nodes["paper"]
    with GNNServer(store, prog.spec, prog.apply_fn(), weights,
                   feature_dim=prog.dim, max_batch=int(s["max_batch"]),
                   batch_window_ms=float(s["batch_window_ms"]),
                   subgraph_cache_size=int(s["subgraph_cache_size"]),
                   embedding_cache_size=int(s["embedding_cache_size"]),
                   base_seed=args.seed) as server:
        print(f"ladder {server.ladder.rungs}", flush=True)
        for rate in [float(r) for r in args.rates.split(",")]:
            # other roots at every rate, so no rate hits the caches
            # that an earlier rate filled
            offsets, roots = loadgen.schedule(
                int(rate), args.seed * 100003 + int(rate), rate,
                args.seconds, n)
            before = server.stats
            start = time.perf_counter()
            sent = loadgen.open_loop(server.submit, offsets, roots, start)
            end = start + args.seconds
            latency, ok = loadgen.harvest(sent, end + 120)
            after = server.stats
            done = np.asarray([x.request.done_at for x in sent])
            late = np.asarray([x.submitted - x.due for x in sent])
            print(json.dumps({
                "rate": rate, "requests": len(sent),
                "answered_in_window_per_s": float(np.sum(ok & (done <= end))
                                                  / args.seconds),
                "p50_ms": 1e3 * float(np.percentile(latency, 50)),
                "p95_ms": 1e3 * float(np.percentile(latency, 95)),
                "max_ms": 1e3 * float(latency.max()),
                "batches": after.batches - before.batches,
                "batch_sizes": {k: after.batch_sizes.get(k, 0)
                                - before.batch_sizes.get(k, 0)
                                for k in after.batch_sizes},
                "submit_late_max_ms": 1e3 * float(late.max())}),
                flush=True)
    del weights
    jax.clear_caches()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
