"""Traced runs of a training cell, read through the program's own spans,
scopes and compile counter (`bench.harness.scopes`).

    python bench/tools/program_spans.py --workload mag_mpnn.train \
        --seeds 11,12 --seconds 30 [--keep out/spans]

Each seed is one ``--trace 1`` run of the cell through the harness
(`runner.run_cell`), with the train step's compiled HLO and compile
count read beside it.  Prints one JSON line per seed: the run's own
result line, the per-layer numbers the program's spans and scopes give,
device time per round and per set updated in each round, what the
acceptance of those spans checks, and the traced step's cycle against
the untraced ones (the cost of tracing).  ``--keep`` writes a sample of
each run's trace and the step's HLO text there.

The run compiles with ``op_name`` metadata in the persistent compile
cache's key: by default JAX leaves it out, so an executable read back
from the cache may carry the names of another build of the same
program (one without the scopes).  The step's HLO is then the
executable that ran, read from JAX's in-memory cache.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import gzip
import json
import re
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

GAP_S = 0.010    # idle gaps longer than this must lie under a program span
ROUND = re.compile(r"^round_\d+(/[^/()]+)?$")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def capture(step_program: str, keep=None):
    """While open, the train step the Trainer builds, its compile count
    after each step, its compiled HLO and the trace's scopes and spans
    are read into the dict it yields."""
    import jax
    from bench.harness import runner, scopes, trace
    from repro import runtime
    from repro.orchestration import trainer as trainer_mod
    got = {}
    make, fit = trainer_mod.make_graph_train_step, trainer_mod.Trainer.fit
    in_key = jax.config.jax_compilation_cache_include_metadata_in_key
    reduce_trace, count = runner.Ctx.reduce_trace, trainer_mod.compile_count

    def make_captured(*a, **kw):
        step = make(*a, **kw)
        got["step"] = step

        def first(*args):
            got.setdefault("shapes", jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return step(*args)
        return first

    def fit_captured(self, model_fn, task, provider, **kw):
        got["clock"] = provider
        result = fit(self, model_fn, task, provider, **kw)
        got["step_compiles"] = result.metrics["step_compiles"]
        return result

    def reduce_captured(ctx, log_dir):
        red = reduce_trace(ctx, log_dir)
        t = time.perf_counter()
        with compiles_seen() as seen:
            hlo = got["step"].lower(*got["shapes"]).compile().as_text()
        got["hlo_read_s"] = time.perf_counter() - t
        # "memory": the executable that ran, from JAX's in-memory cache
        got["hlo_from"] = ("compile" if BACKEND_COMPILE in seen else
                           "persistent cache" if CACHE_HIT in seen
                           else "memory")
        path = trace.find_xplane(log_dir)
        got["scopes_of"] = scopes.op_scopes(hlo)
        got["scoped"] = scopes.reduce_scopes(
            path, got["scopes_of"], program=step_program,
            host_ops=ctx.host_trace)
        if keep:
            out = Path(keep) / f"{ctx.cell}.{ctx.seed}"
            out.mkdir(parents=True, exist_ok=True)
            with open(out / "trace_sample.json", "w") as f:
                json.dump(trace_sample(path), f, indent=1)
            with gzip.open(out / "step_hlo.txt.gz", "wt") as f:
                f.write(hlo)
        return red

    open_gc = []

    def on_gc(phase, info):
        """Older-generation collections as host spans (``bench.gc``):
        they stall the host between the program's spans."""
        if phase == "start" and info["generation"] > 0:
            open_gc.append(jax.profiler.TraceAnnotation("bench.gc"))
            open_gc[-1].__enter__()
        elif phase == "stop" and open_gc:
            open_gc.pop().__exit__(None, None, None)

    # the benchmark's Probe wraps the step in a plain function, which
    # hides the jit cache from the Trainer: count the captured step's
    patches = [(trainer_mod, "make_graph_train_step", make_captured),
               (trainer_mod.Trainer, "fit", fit_captured),
               (runner.Ctx, "reduce_trace", reduce_captured),
               (trainer_mod, "compile_count",
                lambda fn: runtime.compile_count(got.get("step", fn)))]
    for obj, name, value in patches:
        setattr(obj, name, value)
    gc.callbacks.append(on_gc)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        yield got
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          in_key)
        gc.callbacks.remove(on_gc)
        for (obj, name, _), original in zip(
                patches, (make, fit, reduce_trace, count)):
            setattr(obj, name, original)


@contextlib.contextmanager
def compiles_seen():
    """The JAX monitoring events of compiles and persistent-cache hits
    while open."""
    import jax
    seen = set()

    def on_event(name, *args, **kw):
        if name in (BACKEND_COMPILE, CACHE_HIT):
            seen.add(name)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_event)


def trace_sample(path: str, n: int = 40) -> dict:
    """The trace's planes and lines, with the first `n` events of each
    line and their stats: what a reader of the trace looks at by hand
    (the whole trace of a step runs to millions of events)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = []
            for e in line.events:
                events.append([e.name, e.start_ns, e.duration_ns,
                               {k: str(v) for k, v in e.stats}])
                if len(events) == n:
                    break
            lines[line.name] = events
    return out


def readings(got: dict, result: dict) -> dict:
    """What one traced run gives through the program's spans."""
    from bench.harness import scopes
    sc, clock = got["scoped"], got["clock"]
    first, last = clock.traced
    steps = last - first + 1
    compiles = got["step_compiles"]
    w0, w1, _ = clock.window
    window_compiles = (compiles[w1 - 1] - compiles[w0 - 2]
                       if None not in (compiles[w1 - 1], compiles[w0 - 2])
                       else None)
    cycles = [b - a for a, b in zip(clock.done_at, clock.done_at[1:])]
    traced = cycles[first - 2:last - 1]
    # step last + 1 pays for stopping the profiler
    untraced = [c for i, c in enumerate(cycles[clock.warmup - 1:],
                                        start=clock.warmup + 1)
                if not first <= i <= last + 1]
    program = sc.program_s
    opt = sc.scope_seconds("optimizer")
    top = result["breakdown"]["device_ops"][0][0]
    top_scopes = sorted(got["scopes_of"].get(scopes.instruction(top), ()))
    return {
        "metrics": scopes.layer_metrics(sc, traced_steps=steps,
                                        window_compiles=window_compiles),
        "accept": {
            "top_op": top, "top_op_scopes": top_scopes,
            "clip_ms": 1e3 * sc.scope_seconds("clip") / steps,
            "program_ms": 1e3 * program / steps,
            "outside_optimizer_named_share": (
                100 * sc.scope_seconds("init_states", "gnn", "head")
                / (program - opt) if program > opt else None),
            "gaps_over_10ms": [[s, parts] for s, parts in sc.gaps
                               if s > GAP_S],
            "gc_ms": 1e3 * sc.span_s.get("bench.gc", 0.0) / steps,
            "next_ms": 1e3 * sc.span_s.get("bench.next", 0.0) / steps,
            "sample_merge_pad_ms": 1e3 * (
                sc.span_s.get("repro.sample", 0.0)
                + sc.span_s.get("repro.merge_pad", 0.0)) / steps,
            "span_ms": {k: 1e3 * v / steps
                        for k, v in sorted(sc.span_s.items())},
            "idle_under_ms": {k: 1e3 * v / steps
                              for k, v in sorted(sc.idle_under.items())},
            "scope_ms": {k: 1e3 * sc.scope_seconds(k) / steps
                         for k in ("init_states", "gnn", "head", "pool",
                                   "broadcast", "optimizer", "clip",
                                   "update")},
        },
        # rounds, and the sets updated in each (GNNStack, GraphUpdate)
        "round_ms": {k: 1e3 * sc.scope_seconds(k) / steps
                     for k in sorted(sc.scope_iv) if ROUND.match(k)},
        "hlo_read_s": got["hlo_read_s"],
        "hlo_from": got["hlo_from"],
        "step_compiles": compiles,
        "traced_cycle_s": traced,
        "untraced_cycle_median_s": (statistics.median(untraced)
                                    if untraced else None),
        "cycle_s": cycles[clock.warmup - 1:],
        "input_s": clock.input_s[clock.warmup:],
    }


def main() -> int:
    from bench.harness import runner
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()
    w, cfg, traffic, limits, e2e, layers = runner.cell_spec(
        runner.read_benchmark(), args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        with capture(traffic["step_program"], args.keep) as got:
            result = runner.run_cell(
                args.workload, cfg, traffic, limits, seed=seed,
                seconds=args.seconds, trace=True, chips=int(w["chips"]),
                e2e=e2e, layers=layers)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "result": result, **readings(got, result)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
