"""Training driver: `Trainer.fit` with its defaults, fed by the program's
own providers.

``"provider": "store"`` samples each step's roots on demand with
`StoreProvider` (roots drawn without replacement from ``--seed``; padding
from `find_size_constraints` over a fixed sample of subgraphs taken at
set-up).  ``"provider": "presampled"`` reads a fixed pool of subgraphs
drawn once from the dataset seed through `BatcherProvider`, shuffled
into batches from ``--seed``.

One `Trainer.fit` call does everything: its first ``warmup_steps`` steps
(the first compiles) are set-up, and the window runs from the loss
read-back of the last of them to the read-back of the first step that
ends ``--seconds`` later.  The benchmark's provider wrapper holds the
clock: it is asked for step k+1 only after the Trainer has read step
k's loss back.  The first three steps are compared with the reference.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import time

import jax
import numpy as np

from bench.harness import check, dataset, device, trace
from bench.harness.program import Program, flops_fn, real_counts
from bench.harness.runner import Outcome, log

REF_STEPS = 3


class Clock:
    """The provider the Trainer sees: chains the inner provider's epochs,
    records host seconds spent in its iterator and the real and padded
    sizes of each batch, and ends the stream when the window closes."""

    edges_sorted_by_target = True

    def __init__(self, inner, *, warmup: int, seconds: float,
                 profile_dir=None, trace_steps: int = 0):
        self.inner = inner
        self.edges_sorted_by_target = inner.edges_sorted_by_target
        self.warmup = warmup
        self.seconds = seconds
        self.profile_dir = profile_dir
        self.trace_steps = trace_steps
        self.done_at: list = []      # host clock at each step's read-back
        self.input_s: list = []      # host seconds in the inner iterator
        self.counts: list = []       # real nodes/edges per batch
        self.padded: list = []       # (real items, padded capacity)
        self.traced = None           # (first step, last step) traced

    num_steps = 10 ** 9

    def close(self):
        self.inner.close()

    def epoch(self, epoch, *, start_step=0):
        del epoch, start_step
        profile = None
        e = 0
        while True:
            it = iter(self.inner.epoch(e))
            while True:
                t = time.perf_counter()
                with trace.span("next"):
                    item = next(it, None)
                if item is None:
                    break
                self.input_s.append(time.perf_counter() - t)
                self._count(item)
                with trace.span("step"):
                    yield item
                self.done_at.append(time.perf_counter())
                k = len(self.done_at)
                if self.profile_dir and k == self.warmup:
                    profile = trace.Profile(self.profile_dir).__enter__()
                    self.traced = (k + 1, k + self.trace_steps)
                if profile is not None and k == self.traced[1]:
                    profile.__exit__(None, None, None)
                    profile = None
                if k > self.warmup and (self.done_at[-1]
                                        - self.done_at[self.warmup - 1]
                                        >= self.seconds):
                    if profile is not None:
                        profile.__exit__(None, None, None)
                    return
            e += 1

    def _count(self, g):
        c = real_counts(g)
        self.counts.append(c)
        cap = sum(s.capacity for s in g.node_sets.values()) + sum(
            s.capacity for s in g.edge_sets.values())
        self.padded.append((sum(c["nodes"].values())
                            + sum(c["edges"].values()), cap))

    @property
    def window(self):
        """(first, last) step of the window, 1-based, and its seconds."""
        w = self.warmup
        return (w + 1, len(self.done_at),
                self.done_at[-1] - self.done_at[w - 1])


class Probe:
    """Wraps the Trainer's step factory to read, from the step's own
    outputs, what the comparison needs: the optimizer's first moment after
    step 1 (its clipped gradient, times 1 - b1) and the parameters after
    step `REF_STEPS`.  Step 4 on runs unchanged."""

    def __init__(self, p0, b1: float):
        self.p0 = p0
        self.b1 = b1
        self.calls = 0
        self.grad_norms = None
        self.head_grad = None
        self.change_norms = None

    def factory(self, make):
        def make_probed(*a, **kw):
            step = make(*a, **kw)

            def probed(params, opt_state, graph, labels):
                out = step(params, opt_state, graph, labels)
                self.calls += 1
                if self.calls == 1:
                    self.grad_norms = {
                        k: v / (1 - self.b1) for k, v in
                        check.leaf_norms(out[1].m).items()}
                    self.head_grad = jax.tree_util.tree_map(
                        np.asarray, out[1].m["head"])
                if self.calls == REF_STEPS:
                    self.change_norms = check.leaf_norms(
                        jax.tree_util.tree_map(lambda a, b: a - b,
                                               out[0], self.p0))
                return out
            return probed
        return make_probed


@contextlib.contextmanager
def probing(probe: Probe):
    from repro.orchestration import trainer as trainer_mod
    original = trainer_mod.make_graph_train_step
    trainer_mod.make_graph_train_step = probe.factory(original)
    try:
        yield
    finally:
        trainer_mod.make_graph_train_step = original


def provider_for(ctx, store, prog):
    """(provider, its plan, items per epoch, raw(i) -> (root, sampled
    subgraph) of item i) for the traffic's provider."""
    from repro.data import find_size_constraints
    from repro.data.sampling import sample_subgraph, seed_rng
    from repro.orchestration import BatcherProvider, StoreProvider
    cfg, tr = ctx.cfg, ctx.traffic
    batch = int(cfg["train"]["roots_per_step"])
    ds_seed = int(cfg["dataset"]["seed"])
    n_papers = store.num_nodes["paper"]
    t = time.perf_counter()
    if tr["provider"] == "store":
        profile_roots = np.random.default_rng(ds_seed).choice(
            n_papers, int(tr["profile_roots"]), replace=False)
        sizes = find_size_constraints(
            [sample_subgraph(store, prog.spec, int(r), seed_rng(ds_seed,
                                                                int(r)))
             for r in profile_roots], batch)
        provider = StoreProvider(store, prog.spec, np.arange(n_papers),
                                 batch_size=batch, sizes=sizes,
                                 seed=ctx.seed, base_seed=ctx.seed)

        def raw(i):
            r = int(i)
            return r, sample_subgraph(store, prog.spec, r,
                                      seed_rng(ctx.seed, r))
        plan, n_items = provider.plan, n_papers
    else:
        pre = cfg["presampled"]
        pool_roots = np.random.default_rng(ds_seed).choice(
            n_papers, int(pre["pool_roots"]), replace=False)
        pool, pool_built = dataset.load_pool(
            store, prog.spec, pool_roots, int(pre["sample_seed"]),
            dataset.cache_key(cfg["dataset"]), ctx.cache_dir)
        t = ctx.mark("pool_built" if pool_built else "pool_loaded", t)
        sizes = find_size_constraints(pool, batch)
        provider = BatcherProvider(pool, batch, sizes, seed=ctx.seed)

        def raw(i):
            return int(pool_roots[i]), pool[int(i)]
        plan, n_items = provider.batcher.plan, len(pool)
    ctx.mark("sizes", t)
    log(f"padded batch: nodes {dict(sizes.total_num_nodes)}, edges "
        f"{dict(sizes.total_num_edges)}")
    return provider, plan, n_items, raw


def first_steps(plan, n_items, raw) -> tuple:
    """(plain subgraphs, roots) of each of the first `REF_STEPS` steps."""
    order = plan.order(0, n_items)
    graphs, roots = [], []
    for k in range(REF_STEPS):
        picked = [raw(i) for i in plan.step_indices(order, k)]
        roots.append([r for r, _ in picked])
        graphs.append([check.plain_graph(g) for _, g in picked])
    return graphs, roots


def run(ctx) -> Outcome:
    from repro.orchestration import Trainer

    cfg, tr = ctx.cfg, ctx.traffic
    t = time.perf_counter()
    store, built = dataset.load_store(cfg["dataset"], ctx.cache_dir)
    ctx.mark("dataset_built" if built else "dataset_loaded", t)
    prog = Program(cfg, store)
    batch = int(cfg["train"]["roots_per_step"])
    provider, plan, n_items, raw = provider_for(ctx, store, prog)

    t = time.perf_counter()
    weights = prog.make_weights(ctx.seed)
    jax.block_until_ready(weights)
    ctx.mark("weights", t)

    opt = cfg["train"]["optimizer"]
    trainer = Trainer(learning_rate=opt["learning_rate"],
                      warmup_steps=int(opt["warmup_steps"]),
                      total_steps=int(opt["total_steps"]),
                      weight_decay=opt["weight_decay"], max_steps=None,
                      log_every=10 ** 9, eval_at="never")
    profile_dir = ctx.trace_dir() if ctx.trace else None
    warmup = max(int(tr["warmup_steps"]), REF_STEPS)
    clock = Clock(provider, warmup=warmup, seconds=ctx.seconds,
                  profile_dir=profile_dir,
                  trace_steps=int(tr["trace_steps"]))
    probe = Probe(weights, float(opt["b1"]))
    t_fit = time.perf_counter()
    with probing(probe):
        result = trainer.fit(prog.model_fn(weights),
                             prog.preset_task(weights), clock)
    provider.close()
    first, last, window_s = clock.window
    ctx.setup["compile_and_warmup"] = clock.done_at[warmup - 1] - t_fit
    setup_s = clock.done_at[warmup - 1] - ctx.t0
    losses = result.metrics["train_losses"]
    steps = last - first + 1
    log(f"window: steps {first}-{last}, {window_s:.3f} s; losses "
        f"{losses[:REF_STEPS]} ... {losses[-1]}")
    peak = device.memory_peak_bytes(ctx.devices)
    reduced = None
    if profile_dir:
        reduced = ctx.reduce_trace(profile_dir)
        shutil.rmtree(profile_dir, ignore_errors=True)
    p0 = jax.tree_util.tree_map(np.asarray, weights)
    del result, weights, probe.p0
    gc.collect()

    # the reference follows the first steps, on the same roots
    t_ref = time.perf_counter()
    step_graphs, step_roots = first_steps(plan, n_items, raw)
    checks = check.Checks(ctx.limits)
    index = check.StoreIndex(store)
    checks.add("sample_faults", sum(
        check.sample_faults(index, prog.spec, g, r)
        for g, r in zip(step_graphs, step_roots)))
    ref = reference_steps(cfg, store, p0, step_graphs, "highest")
    for name, value in gaps(
            (losses[:REF_STEPS], probe.grad_norms, probe.head_grad,
             probe.change_norms), ref).items():
        if name in ctx.limits:
            checks.add(name, value)
    log(f"reference: losses {ref[0]}; {time.perf_counter() - t_ref:.1f}"
        " s (not in set-up)")

    flops = flops_fn(cfg, store.schema)
    traced = clock.traced
    records = {
        "units": {"train_roots_per_s": "roots/s"},
        "kind": "train", "window_s": window_s, "steps": steps,
        "input_s": clock.input_s[first - 1:last],
        "padded": clock.padded[first - 1:last],
        "train_flops": 3 * sum(flops(c)
                               for c in clock.counts[first - 1:last]),
        "traced_steps": (traced[1] - traced[0] + 1) if traced else 0,
        "step_program": tr["step_program"],
    }
    return Outcome(e2e={"train_roots_per_s": steps * batch / window_s,
                        "setup_s": setup_s},
                   records=records, checks=checks, attempted=steps,
                   failed=0, memory_peak_bytes=peak, reduced=reduced)


def gaps(program: tuple, ref: tuple) -> dict:
    """The compared numbers (a cell compares those its limits file
    names): the worst relative gap of the first steps' losses; the worst
    leaf gap of the first clipped gradient's norms; the direction gap of
    the readout head's first gradient; and the median leaf's gap of the
    norms of the parameters' change after the first steps, over the
    leaves that the reference's gradient moves.

    The head's gradient depends on the forward values alone, so unlike
    the deeper leaves it has no ReLU derivative that float32 round-off
    can flip, and its direction is free of the clip's scale.  The worst
    changed leaf swings with Adam's response to round-off in the later
    steps, hence the median; PERF.md gives both readings."""
    losses, grad_norms, head, change_norms = program
    ref_losses, ref_grad, ref_head, ref_change = ref
    moved = check.moved_leaves(ref_grad)
    change = check.leaf_gaps(change_norms, ref_change, moved)
    still = sorted(set(ref_grad) - moved)
    log(f"change gaps over {len(moved)} of {len(ref_grad)} leaves; the "
        f"reference's gradient leaves {len(still)} out: {still}")
    log(f"clipped step-1 gradient global norms: "
        f"{np.sqrt(sum(v * v for v in grad_norms.values())):.9g} vs "
        f"{np.sqrt(sum(v * v for v in ref_grad.values())):.9g}")
    log(f"loss gaps {[abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]}")
    log("worst gradient leaves: "
        + check.worst_leaves(grad_norms, ref_grad))
    log(f"change gaps: worst leaf {max(change.values()):.6g}: "
        + check.worst_leaves(change_norms, ref_change, moved))
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, ref_losses)),
            "grad_gap": max(check.leaf_gaps(grad_norms, ref_grad).values()),
            "head_grad_gap": max(
                check.direction_gap(head[k], ref_head[k]) for k in ref_head),
            "change_gap": float(np.median(list(change.values())))}


def control(ctx) -> dict:
    """The control: the reference with its matmuls at 'high' (three
    bfloat16 passes), one step below the configuration's 'highest', in
    the program's place on the cell's own first steps, against the
    reference."""
    store, _ = dataset.load_store(ctx.cfg["dataset"], ctx.cache_dir)
    prog = Program(ctx.cfg, store)
    provider, plan, n_items, raw = provider_for(ctx, store, prog)
    provider.close()
    p0 = jax.tree_util.tree_map(np.asarray, prog.make_weights(ctx.seed))
    step_graphs, _ = first_steps(plan, n_items, raw)
    low = reference_steps(ctx.cfg, store, p0, step_graphs, "high")
    ref = reference_steps(ctx.cfg, store, p0, step_graphs, "highest")
    return gaps(low, ref)


def reference_steps(cfg, store, p0, step_graphs, precision) -> tuple:
    """The reference's first steps on the host's CPU, with its own merge,
    loss, gradient and AdamW: (losses, leaf norms of the first clipped
    gradient, the head's first clipped gradient, leaf norms of the
    parameters' change)."""
    from bench.references import common
    cpu = jax.devices("cpu")[0]
    ref = check.reference_module(cfg)
    schema_edges = {n: (e.source, e.target)
                    for n, e in store.schema.edge_sets.items()}

    def loss_fn(p, b):
        logits = ref.forward(p, b, schema_edges=schema_edges,
                             model=cfg["model"], precision=precision)
        return common.cross_entropy(logits, b["labels"])

    batches = []
    for graphs in step_graphs:
        b = common.merge(graphs, schema_edges)
        b["labels"] = b["nodes"]["paper"]["labels"][b["roots"]]
        batches.append(b)
    losses, grad, params = common.train(
        loss_fn, p0, batches, cfg["train"]["optimizer"], cpu)
    return (losses, check.leaf_norms(grad),
            jax.tree_util.tree_map(np.asarray, grad["head"]),
            check.leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b,
                                                    params, p0)))
