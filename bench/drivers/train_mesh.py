"""Training driver over a device mesh: `Trainer.fit` at ``num_devices``
(a ``num_devices`` x 1 data mesh, ZeRO-1 optimizer state), fed by the
program's own providers, with the step's own scopes read on a traced
run.

``"provider": "presampled"`` is the train driver's provider, on one
device: a fixed pool of subgraphs drawn once from the dataset seed,
read through `BatcherProvider`, shuffled from ``--seed``.
``"provider": "service"`` samples each step's roots (a permutation of
every paper from ``--seed``) in a forked `SamplingService` of
``sampler_workers`` workers behind `ServiceProvider`, padded to sizes
found over ``profile_roots`` subgraphs at set-up; each step is a
super-batch of ``num_devices`` groups of the configuration's
``roots_per_step`` roots, one group per chip.

The window, the comparison with the reference (which merges each step's
whole global batch) and the records are those of the ``train`` driver,
whose `Clock`, `Probe`, `probing`, `provider_for`, `first_steps`, `gaps`
and `reference_steps` this driver uses. A traced run also reads which
`jax.named_scope`s each operation of the step ran under, from the
``op_name`` metadata of the step's compiled HLO (the executable that
ran, from JAX's in-memory cache; the persistent cache's key holds the
metadata during such a run, so a build without the scopes is not read
back), and records device milliseconds per traced step under each of the
traffic's ``scopes``.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import time

import jax
import numpy as np

from bench.harness import check, dataset, device, scopes, trace
from bench.harness.program import (BENCH, Program, flops_fn, load_module,
                                   real_counts)
from bench.harness.runner import Outcome, log

train = load_module(BENCH / "drivers" / "train.py")
REF_STEPS = train.REF_STEPS


class GroupClock(train.Clock):
    """The train driver's `Clock`, counting a super-batch's groups."""

    def _count(self, g):
        from repro.core.graph_tensor import stack_size, unstack_graph
        if stack_size(g) is None:
            return super()._count(g)
        groups = unstack_graph(g)
        counts = [real_counts(x) for x in groups]
        self.counts.append({
            "nodes": {n: sum(c["nodes"][n] for c in counts)
                      for n in counts[0]["nodes"]},
            "edges": {n: sum(c["edges"][n] for c in counts)
                      for n in counts[0]["edges"]},
            "components": sum(c["components"] for c in counts)})
        cap = sum(s.capacity for s in g.node_sets.values()) + sum(
            s.capacity for s in g.edge_sets.values())
        real = sum(sum(c["nodes"].values()) + sum(c["edges"].values())
                   for c in counts)
        self.padded.append((real, cap * len(groups)))


@contextlib.contextmanager
def probing(probe, got: dict):
    """The train driver's `probing`, with the probe's step factory
    wrapped to keep the jitted step and the shapes and shardings of its
    first arguments in `got`."""
    factory = probe.factory

    def keeping(make):
        def make_kept(*a, **kw):
            step = make(*a, **kw)
            got["step"] = step

            def first(*args):
                if "args" not in got:
                    got["args"] = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding), args)
                return step(*args)
            return first
        return factory(make_kept)

    probe.factory = keeping
    try:
        with train.probing(probe):
            yield
    finally:
        del probe.factory


def provider_for(ctx, store, prog, num_devices: int):
    """(provider, its plan, items per epoch, raw(i) -> (root, sampled
    subgraph) of item i) for the traffic's provider: ``presampled`` is
    the train driver's, on one device."""
    from repro.data import find_size_constraints
    from repro.data.sampling import sample_subgraph, seed_rng
    from repro.orchestration import ServiceProvider
    from repro.sampling_service import SamplingService
    cfg, tr = ctx.cfg, ctx.traffic
    if tr["provider"] == "presampled":
        if num_devices != 1:
            raise ValueError("the presampled provider feeds one device")
        return train.provider_for(ctx, store, prog)
    if tr["provider"] != "service":
        raise ValueError(f"no provider {tr['provider']!r}")
    per_group = int(cfg["train"]["roots_per_step"])
    ds_seed = int(cfg["dataset"]["seed"])
    n_papers = store.num_nodes["paper"]
    t = time.perf_counter()
    profile_roots = np.random.default_rng(ds_seed).choice(
        n_papers, int(tr["profile_roots"]), replace=False)
    sizes = find_size_constraints(
        [sample_subgraph(store, prog.spec, int(r), seed_rng(ds_seed, int(r)))
         for r in profile_roots], per_group)
    service = SamplingService(
        store, prog.spec, np.arange(n_papers),
        batch_size=per_group * num_devices, sizes=sizes,
        num_workers=int(tr["sampler_workers"]), num_replicas=num_devices,
        seed=ctx.seed, base_seed=ctx.seed, edges_sorted_by_target=True)
    ctx.mark("sizes", t)
    log(f"padded group ({num_devices} per step): nodes "
        f"{dict(sizes.total_num_nodes)}, edges {dict(sizes.total_num_edges)}")

    def raw(i):
        r = int(i)
        return r, sample_subgraph(store, prog.spec, r, seed_rng(ctx.seed, r))
    return ServiceProvider(service, own=True), service.plan, n_papers, raw


def scope_ms(ctx, got: dict, profile_dir: str, traced_steps: int) -> dict:
    """Device ms per traced step under each of the traffic's scopes that
    the step's HLO names (a scope the program lacks gives none)."""
    t = time.perf_counter()
    hlo = got["step"].lower(*got["args"]).compile().as_text()
    sc = scopes.reduce_scopes(trace.find_xplane(profile_dir),
                              scopes.op_scopes(hlo),
                              program=ctx.traffic["step_program"],
                              host_ops=ctx.host_trace)
    out = {s: 1e3 * sc.scope_seconds(s) / traced_steps
           for s in ctx.traffic["scopes"] if s in sc.scope_iv}
    log(f"scopes read in {time.perf_counter() - t:.2f} s: {out}")
    return out


def run(ctx) -> Outcome:
    num_devices = int(ctx.traffic["num_devices"])
    if len(ctx.devices) < num_devices:
        raise device.NoChip(f"the traffic asks for {num_devices} devices "
                            f"and the run has {len(ctx.devices)}")
    in_key = jax.config.jax_compilation_cache_include_metadata_in_key
    if ctx.trace:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    try:
        return _run(ctx, num_devices)
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          in_key)


def _run(ctx, num_devices: int) -> Outcome:
    from repro.orchestration import Trainer

    cfg, tr = ctx.cfg, ctx.traffic
    t = time.perf_counter()
    store, built = dataset.load_store(cfg["dataset"], ctx.cache_dir)
    ctx.mark("dataset_built" if built else "dataset_loaded", t)
    prog = Program(cfg, store)
    batch = int(cfg["train"]["roots_per_step"]) * num_devices
    provider, plan, n_items, raw = provider_for(ctx, store, prog,
                                                num_devices)

    t = time.perf_counter()
    weights = prog.make_weights(ctx.seed)
    jax.block_until_ready(weights)
    ctx.mark("weights", t)

    opt = cfg["train"]["optimizer"]
    trainer = Trainer(learning_rate=opt["learning_rate"],
                      warmup_steps=int(opt["warmup_steps"]),
                      total_steps=int(opt["total_steps"]),
                      weight_decay=opt["weight_decay"], max_steps=None,
                      log_every=10 ** 9, eval_at="never",
                      num_devices=num_devices)
    profile_dir = ctx.trace_dir() if ctx.trace else None
    warmup = max(int(tr["warmup_steps"]), REF_STEPS)
    clock = GroupClock(provider, warmup=warmup, seconds=ctx.seconds,
                       profile_dir=profile_dir,
                       trace_steps=int(tr["trace_steps"]))
    # the step donates its parameters, and on one device placing them
    # may keep these very buffers: the comparison keeps a host copy
    p0 = jax.tree_util.tree_map(np.asarray, weights)
    probe = train.Probe(p0, float(opt["b1"]))
    got: dict = {}
    t_fit = time.perf_counter()
    try:
        with probing(probe, got):
            result = trainer.fit(prog.model_fn(weights),
                                 prog.preset_task(weights), clock)
    finally:
        provider.close()
    first, last, window_s = clock.window
    ctx.setup["compile_and_warmup"] = clock.done_at[warmup - 1] - t_fit
    setup_s = clock.done_at[warmup - 1] - ctx.t0
    losses = result.metrics["train_losses"]
    steps = last - first + 1
    log(f"window: steps {first}-{last}, {window_s:.3f} s; losses "
        f"{losses[:REF_STEPS]} ... {losses[-1]}")
    peak = device.memory_peak_bytes(ctx.devices)
    traced = clock.traced
    traced_steps = (traced[1] - traced[0] + 1) if traced else 0
    reduced, scoped = None, {}
    if profile_dir:
        reduced = ctx.reduce_trace(profile_dir)
        scoped = scope_ms(ctx, got, profile_dir, traced_steps)
        shutil.rmtree(profile_dir, ignore_errors=True)
    del result, weights, probe.p0, got
    gc.collect()

    # the reference follows the first steps, on the same roots, each
    # step's groups merged into one batch
    t_ref = time.perf_counter()
    step_graphs, step_roots = train.first_steps(plan, n_items, raw)
    checks = check.Checks(ctx.limits)
    index = check.StoreIndex(store)
    checks.add("sample_faults", sum(
        check.sample_faults(index, prog.spec, g, r)
        for g, r in zip(step_graphs, step_roots)))
    ref = train.reference_steps(cfg, store, p0, step_graphs, "highest")
    for name, value in train.gaps(
            (losses[:REF_STEPS], probe.grad_norms, probe.head_grad,
             probe.change_norms), ref).items():
        if name in ctx.limits:
            checks.add(name, value)
    log(f"reference: losses {ref[0]}; {time.perf_counter() - t_ref:.1f}"
        " s (not in set-up)")

    flops = flops_fn(cfg, store.schema)
    records = {
        "units": {"train_roots_per_s": "roots/s"},
        "kind": "train", "window_s": window_s, "steps": steps,
        "input_s": clock.input_s[first - 1:last],
        "padded": clock.padded[first - 1:last],
        "train_flops": 3 * sum(flops(c)
                               for c in clock.counts[first - 1:last]),
        "traced_steps": traced_steps,
        "step_program": tr["step_program"],
        "scope_ms": scoped,
    }
    return Outcome(e2e={"train_roots_per_s": steps * batch / window_s,
                        "setup_s": setup_s},
                   records=records, checks=checks, attempted=steps,
                   failed=0, memory_peak_bytes=peak, reduced=reduced)


def control(ctx) -> dict:
    """The control: the reference with its matmuls at 'high' (three
    bfloat16 passes), one step below the configuration's 'highest', in
    the program's place on the cell's own first steps, against the
    reference."""
    store, _ = dataset.load_store(ctx.cfg["dataset"], ctx.cache_dir)
    prog = Program(ctx.cfg, store)
    provider, plan, n_items, raw = provider_for(
        ctx, store, prog, int(ctx.traffic["num_devices"]))
    provider.close()
    p0 = jax.tree_util.tree_map(np.asarray, prog.make_weights(ctx.seed))
    step_graphs, _ = train.first_steps(plan, n_items, raw)
    low = train.reference_steps(ctx.cfg, store, p0, step_graphs, "high")
    ref = train.reference_steps(ctx.cfg, store, p0, step_graphs, "highest")
    return train.gaps(low, ref)
