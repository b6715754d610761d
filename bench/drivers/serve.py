"""Serving driver: a warmed `GNNServer` with the configuration's settings,
under open-loop Poisson arrivals at the traffic file's fixed rate.

Set-up builds the server (its warm-up compiles every bucket) and sends
``warmup_seconds`` of the same traffic with other roots, untimed.  The
window then offers ``rate_per_s`` x ``--seconds`` requests due at times
drawn from the traffic file's ``arrival_seed``, roots uniform over all
papers drawn from ``--seed``.  Each request is
timed from its due time to its answer; those still open when the window
closes are waited for, up to ``harvest_seconds`` more.  Afterwards a
seeded sample of the answered requests is compared with the plain
reference's forward of each root's subgraph.
"""
from __future__ import annotations

import gc
import shutil
import time

import jax
import numpy as np

from bench.harness import check, dataset, device, loadgen, trace
from bench.harness.program import Program, flops_fn, real_counts
from bench.harness.runner import Outcome, log


def run(ctx) -> Outcome:
    from repro.data.sampling import sample_subgraph, seed_rng
    from repro.serve import GNNServer

    cfg, tr = ctx.cfg, ctx.traffic
    t = time.perf_counter()
    store, built = dataset.load_store(cfg["dataset"], ctx.cache_dir)
    t = ctx.mark("dataset_built" if built else "dataset_loaded", t)
    prog = Program(cfg, store)
    weights = prog.make_weights(ctx.seed)
    jax.block_until_ready(weights)
    t = ctx.mark("weights", t)

    n_papers = store.num_nodes["paper"]
    rate = float(tr["rate_per_s"])
    arrivals = int(tr["arrival_seed"])
    warm_rng = np.random.default_rng((ctx.seed, 1))
    s = cfg["serve"]
    server = GNNServer(store, prog.spec, prog.apply_fn(), weights,
                       feature_dim=prog.dim, max_batch=int(s["max_batch"]),
                       batch_window_ms=float(s["batch_window_ms"]),
                       subgraph_cache_size=int(s["subgraph_cache_size"]),
                       embedding_cache_size=int(s["embedding_cache_size"]),
                       base_seed=ctx.seed,
                       warmup_root=int(warm_rng.integers(n_papers)))
    ladder_max = server.ladder.max_batch
    log(f"bucket ladder {server.ladder.rungs}")
    try:
        t = ctx.mark("compile", t)
        offsets, roots = loadgen.schedule(
            arrivals + 1, int(warm_rng.integers(2 ** 63)), rate,
            float(tr["warmup_seconds"]), n_papers)
        warm = loadgen.open_loop(server.submit, offsets, roots,
                                 time.perf_counter())
        loadgen.harvest(warm, time.perf_counter()
                        + float(tr["harvest_seconds"]))
        ctx.mark("warmup", t)

        offsets, roots = loadgen.schedule(arrivals, ctx.seed, rate,
                                          ctx.seconds, n_papers)
        # Set-up's objects leave the collector's generations, so that a
        # full collection in the window scans only what the window made.
        gc.collect()
        gc.freeze()
        profile_dir = ctx.trace_dir() if ctx.trace else None
        profile = (trace.Profile(profile_dir).__enter__()
                   if profile_dir else None)
        before = server.stats
        start = time.perf_counter()
        setup_s = start - ctx.t0
        sent = loadgen.open_loop(server.submit, offsets, roots, start)
        end = start + ctx.seconds
        time.sleep(max(end - time.perf_counter(), 0.0))
        after = server.stats
        if profile is not None:
            profile.__exit__(None, None, None)
        latency, ok = loadgen.harvest(sent, end
                                      + float(tr["harvest_seconds"]))
        peak = device.memory_peak_bytes(ctx.devices)
    finally:
        server.close()
    late = np.asarray([x.submitted - x.due for x in sent])
    done = np.asarray([x.request.done_at or np.inf for x in sent])
    answered = int(np.sum(ok & (done <= end)))
    log(f"window: {len(sent)} requests due in {ctx.seconds} s at "
        f"{rate} /s; {int(ok.sum())} answered ({answered} inside the "
        f"window); p95 {1e3 * np.percentile(latency, 95):.3f} ms from the "
        f"due time; submitter late by median "
        f"{1e3 * np.median(late):.3f} ms, max {1e3 * late.max():.3f} ms")
    reduced = None
    if profile_dir:
        reduced = ctx.reduce_trace(profile_dir)
        shutil.rmtree(profile_dir, ignore_errors=True)

    # a seeded sample of the answered requests against the reference
    t_ref = time.perf_counter()
    checks = check.Checks(ctx.limits)
    checks.add("unanswered", int(np.sum(~ok)))
    idx = np.flatnonzero(ok)
    pick = np.random.default_rng((ctx.seed, 2)).choice(
        idx, min(len(idx), int(tr["check_requests"])), replace=False)
    p0 = jax.tree_util.tree_map(np.asarray, weights)
    del weights, server
    gc.collect()
    graphs = [sample_subgraph(store, prog.spec, int(roots[i]),
                              seed_rng(ctx.seed, int(roots[i])))
              for i in pick]
    plain = [check.plain_graph(g) for g in graphs]
    checks.add("sample_faults", check.sample_faults(
        check.StoreIndex(store), prog.spec, plain,
        [int(roots[i]) for i in pick]))
    served = np.stack([np.asarray(sent[i].request.result(0))
                       for i in pick])
    want = reference_logits(cfg, store, p0, plain, "highest")
    checks.add("logit_gap", logit_gap(served, want))
    log(f"reference: {len(pick)} requests in "
        f"{time.perf_counter() - t_ref:.1f} s (not in set-up)")

    records = {
        "units": {"serve_rps": "req/s"},
        "kind": "serve", "window_s": ctx.seconds,
        "latency_p95_ms": 1e3 * float(np.percentile(latency, 95)),
        "served": after.served - before.served,
        "batches": after.batches - before.batches,
        "max_batch": ladder_max,
        "forward_program": tr["forward_program"],
        "serve_flops": serve_flops(cfg, store, prog.spec, roots, ok, done,
                                   end, ctx.seed) if ctx.trace else 0.0,
    }
    return Outcome(e2e={"serve_rps": answered / ctx.seconds,
                        "setup_s": setup_s},
                   records=records, checks=checks, attempted=len(sent),
                   failed=int(np.sum(~ok)), memory_peak_bytes=peak,
                   reduced=reduced)


def logit_gap(served: np.ndarray, want: np.ndarray) -> float:
    """Widest gap between a served and a reference logit, over the larger
    of that row's largest reference logit and the median row's."""
    scale = np.abs(want).max(axis=1)
    floor = float(np.median(scale))
    return float(np.max(np.abs(served - want).max(axis=1)
                        / np.maximum(scale, floor)))


def reference_logits(cfg, store, p0, plain, precision) -> np.ndarray:
    from bench.references import common
    ref = check.reference_module(cfg)
    schema_edges = {n: (e.source, e.target)
                    for n, e in store.schema.edge_sets.items()}
    batch = common.merge(plain, schema_edges)
    return common.logits(
        lambda p, b: ref.forward(p, b, schema_edges=schema_edges,
                                 model=cfg["model"], precision=precision),
        p0, batch, jax.devices("cpu")[0])


def serve_flops(cfg, store, spec, roots, ok, done, end, seed) -> float:
    """Forward FLOPs of the real subgraphs of the requests answered in
    the window (re-sampled: the sampler is deterministic per root)."""
    from repro.data.sampling import sample_subgraph, seed_rng
    flops = flops_fn(cfg, store.schema)
    return sum(flops(real_counts(sample_subgraph(
        store, spec, int(roots[i]), seed_rng(seed, int(roots[i]))),
        padded=False)) for i in np.flatnonzero(ok & (done <= end)))


def control(ctx) -> dict:
    """The control: the reference with its matmuls at 'high' (three
    bfloat16 passes), one step below the configuration's 'highest', in
    the program's place on the requests a run of this seed checks,
    against the reference."""
    from repro.data.sampling import sample_subgraph, seed_rng
    store, _ = dataset.load_store(ctx.cfg["dataset"], ctx.cache_dir)
    prog = Program(ctx.cfg, store)
    p0 = jax.tree_util.tree_map(np.asarray, prog.make_weights(ctx.seed))
    _, roots = loadgen.schedule(int(ctx.traffic["arrival_seed"]), ctx.seed,
                                float(ctx.traffic["rate_per_s"]),
                                ctx.seconds, store.num_nodes["paper"])
    pick = np.random.default_rng((ctx.seed, 2)).choice(
        len(roots), min(len(roots), int(ctx.traffic["check_requests"])),
        replace=False)
    plain = [check.plain_graph(sample_subgraph(
        store, prog.spec, int(roots[i]), seed_rng(ctx.seed, int(roots[i]))))
        for i in pick]
    low = reference_logits(ctx.cfg, store, p0, plain, "high")
    want = reference_logits(ctx.cfg, store, p0, plain, "highest")
    return {"logit_gap": logit_gap(low, want)}
