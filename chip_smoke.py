"""Bring-up smoke test: the main path, once, on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the mesh trainers, on four chips

The graph is `synthetic_mag` at OGBN-MAG's published node counts (OGB,
arXiv:2005.00687): 736,389 papers, 1,134,649 authors, 8,740
institutions and 59,965 fields, 128-d paper features, 349 classes, all
drawn from ``--seed``.  The model is the paper's §8 VanillaMPNN at the
widths `vanilla_mpnn` defaults to (4 rounds, message and hidden dim 128,
layer norm) with a root-paper classification head; featureless node
sets get a full-size embedding table.  Each step trains on 128 roots
with the sampling spec of ``examples/ogbn_mag_train.py``.  Weights are
random, from the seed.

One chip, in one process and in this order:

  1. device check: the platform must be ``tpu``; there is no fallback;
  2. train, reference path: `Trainer.fit` for 5 steps with kernels off,
     batches sampled on demand by `StoreProvider`;
  3. train, kernel path: the same 5 steps with the Pallas kernels on.
     Prints every round's `describe_dispatch`; fails unless a kernel
     ran, none in interpret mode, and the losses agree with step 2's
     within ``LOSS_RTOL``;
  4. train, sampler fleet: 3 steps fed by a forked `SamplingService`
     (the parent holds the chip); the losses must equal step 2's first 3;
  5. serve: a warmed `GNNServer` answers 32 requests; each answer must
     match a direct forward of that root's subgraph, with zero
     recompiles after warm-up.

``--chips 4`` runs only the mesh path: the trainer at 4x1 and 2x2
("data" x "model"), each checked to span four distinct devices, against
a 1-device run of the same steps in the same process, all three with
fp32 matmuls (see `phase_mesh`).

Any failure raises, so the exit code is non-zero and the result line is
not printed.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Host timings printed on the way are from one bring-up run, not
measurements.  The phase functions run on the CPU too, at a tiny graph
(tests/test_chip_smoke.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import HIDDEN_STATE, mag_schema  # noqa: E402
from repro.core.models import vanilla_mpnn  # noqa: E402
from repro.data import (BatchPlan, SamplingSpecBuilder,  # noqa: E402
                        SizeConstraints, merge_and_pad, sample_subgraph)
from repro.data.sampling import seed_rng  # noqa: E402
from repro.data.synthetic import synthetic_mag  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.nn.layers import Embedding, Linear  # noqa: E402
from repro.nn.module import Module  # noqa: E402
from repro.orchestration import (DatasetProvider,  # noqa: E402
                                 RootNodeMulticlassClassification,
                                 ServiceProvider, StoreProvider, Trainer)

# OGBN-MAG's published shape (OGB, arXiv:2005.00687)
MAG = dict(n_papers=736_389, n_authors=1_134_649, n_institutions=8_740,
           n_fields=59_965, feat_dim=128, n_classes=349)
HIDDEN = 128          # vanilla_mpnn's message and hidden dim defaults
ROUNDS = 4
ROOTS_PER_STEP = 128
TRAIN_STEPS = 5
FLEET_STEPS = 3
SERVE_REQUESTS = 32
# Kernel path vs reference path, and mesh vs one device: the same math
# summed in another order.  fp32 rounding moves the loss by ~1e-6
# relative; a wrong pool or a lost shard moves it by far more than 1e-4.
LOSS_RTOL = 1e-4
# served (micro-batched) vs direct (alone) logits, same kernel toggle
SERVE_RTOL = SERVE_ATOL = 1e-4


class SmokeError(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# ---------------------------------------------------------------------------
# Set-up: graph, sampling spec, model, task, padding
# ---------------------------------------------------------------------------

class InitStates(Module):
    """Paper features -> hidden states; an embedding table per
    featureless node set, one row per node."""

    def __init__(self, feat_dim: int, dim: int, rows: dict):
        self.paper = Linear(feat_dim, dim)
        self.tables = {n: Embedding(r, dim) for n, r in sorted(rows.items())}

    def init(self, key):
        keys = jax.random.split(key, len(self.tables) + 1)
        p = {"paper": self.paper.init(keys[0])}
        for k, (n, t) in zip(keys[1:], self.tables.items()):
            p[n] = t.init(k)
        return p

    def __call__(self, params, graph):
        ns = {"paper": {HIDDEN_STATE: jax.nn.relu(self.paper(
            params["paper"], graph.node_sets["paper"]["feat"]))}}
        for n, t in self.tables.items():
            ns[n] = {HIDDEN_STATE: t(params[n], graph.node_sets[n]["id"],
                                     dtype=jnp.float32)}
        return graph.replace_features(node_sets=ns)


def mag_spec(schema):
    """The sampling spec of examples/ogbn_mag_train.py (paper Fig. 6)."""
    b = SamplingSpecBuilder(schema)
    seed_op = b.seed("paper")
    cited = seed_op.sample(8, "cites")
    authors = cited.join([seed_op]).sample(4, "written")
    author_papers = authors.sample(4, "writes")
    authors.sample(4, "affiliated_with")
    author_papers.join([seed_op, cited]).sample(4, "has_topic")
    return seed_op.build()


def tight_sizes(store, spec, roots, plan: BatchPlan,
                base_seed: int) -> SizeConstraints:
    """Capacities that hold every component group of the steps epoch 0
    draws from `roots`, plus one padding node and edge per set: tight
    constraints over the batches this run trains on, found by sampling
    them once."""
    order = plan.order(0, len(roots))
    nodes: dict = {}
    edges: dict = {}
    for step in range(plan.num_steps(len(roots))):
        graphs = [sample_subgraph(store, spec, int(roots[i]),
                                  seed_rng(base_seed, int(roots[i])))
                  for i in plan.step_indices(order, step)]
        for lo in range(0, len(graphs), plan.per_group):
            group = graphs[lo:lo + plan.per_group]
            for name in group[0].node_sets:
                nodes[name] = max(nodes.get(name, 0), sum(
                    g.node_sets[name].capacity for g in group))
            for name in group[0].edge_sets:
                edges[name] = max(edges.get(name, 0), sum(
                    g.edge_sets[name].capacity for g in group))
    return SizeConstraints(
        total_num_components=plan.per_group + 1,
        total_num_nodes={k: v + 1 for k, v in nodes.items()},
        total_num_edges={k: v + 1 for k, v in edges.items()})


@dataclasses.dataclass
class Setup:
    store: object
    spec: object
    roots: np.ndarray          # training roots: exactly one epoch of steps
    sizes: SizeConstraints     # per 128-root batch
    feat_dim: int
    dim: int
    rounds: int
    n_classes: int
    roots_per_step: int
    seed: int

    @property
    def task(self):
        return RootNodeMulticlassClassification("paper", self.n_classes,
                                                self.dim)

    def model_fn(self):
        rows = {n: self.store.num_nodes[n]
                for n in ("author", "institution", "field_of_study")}
        edges = {name: (es.source, es.target)
                 for name, es in self.store.schema.edge_sets.items()}
        gnn = vanilla_mpnn(edges, {n: self.dim for n in self.store.num_nodes},
                           message_dim=self.dim, hidden_dim=self.dim,
                           num_rounds=self.rounds, use_layer_norm=True)
        return InitStates(self.feat_dim, self.dim, rows), gnn

    def plan(self, num_replicas=None) -> BatchPlan:
        return BatchPlan(self.roots_per_step, seed=self.seed,
                         num_replicas=num_replicas)

    def provider(self, sizes=None, num_replicas=None) -> StoreProvider:
        return StoreProvider(self.store, self.spec, self.roots,
                             batch_size=self.roots_per_step,
                             sizes=sizes or self.sizes, seed=self.seed,
                             num_replicas=num_replicas,
                             base_seed=self.seed)

    def trainer(self, steps: int, **kw) -> Trainer:
        # warmup_steps=0: the loss moves from the first step on
        return Trainer(learning_rate=1e-3, warmup_steps=0, total_steps=1000,
                       seed=self.seed, max_steps=steps, log_every=10 ** 9,
                       eval_at="never", **kw)


def build_setup(*, seed: int, n_papers: int, n_authors: int,
                n_institutions: int, n_fields: int, feat_dim: int,
                n_classes: int, dim: int, rounds: int, roots_per_step: int,
                steps: int) -> Setup:
    t0 = time.perf_counter()
    store, _ = synthetic_mag(n_papers=n_papers, n_authors=n_authors,
                             n_institutions=n_institutions,
                             n_fields=n_fields, n_classes=n_classes,
                             feat_dim=feat_dim, seed=seed)
    spec = mag_spec(store.schema)
    roots = np.random.default_rng(seed).choice(
        n_papers, steps * roots_per_step, replace=False)
    sizes = tight_sizes(store, spec, roots,
                        BatchPlan(roots_per_step, seed=seed), seed)
    print(f"graph: {store.num_nodes} nodes, "
          f"{ {k: len(v[0]) for k, v in store.edges.items()} } edges, "
          f"built in {time.perf_counter() - t0:.1f} s; padded batch "
          f"{dict(sizes.total_num_nodes)} nodes", flush=True)
    return Setup(store, spec, roots, sizes, feat_dim, dim, rounds,
                 n_classes, roots_per_step, seed)


# ---------------------------------------------------------------------------
# Training runs
# ---------------------------------------------------------------------------

class _Timed(DatasetProvider):
    """Keeps the first batch, and the host seconds from handing each
    batch over to being asked for the next (placement + step + loss
    read-back; the first includes compilation)."""

    def __init__(self, inner):
        self.inner = inner
        self.edges_sorted_by_target = inner.edges_sorted_by_target
        self.first_batch = None
        self.step_seconds: list = []

    @property
    def num_steps(self):
        return self.inner.num_steps

    def epoch(self, epoch, *, start_step=0):
        for item in self.inner.epoch(epoch, start_step=start_step):
            if self.first_batch is None:
                self.first_batch = item
            t = time.perf_counter()
            yield item
            self.step_seconds.append(time.perf_counter() - t)


@dataclasses.dataclass
class Run:
    losses: list
    step_seconds: list
    params: dict
    decisions: list   # [(round, node_set, edge_set, Decision | None)]


def dispatch_decisions(setup: Setup, params, graph) -> list:
    """Every round's `GraphUpdate.describe_dispatch` on `graph`, traced
    abstractly (decisions depend on shapes only)."""
    init_states, gnn = setup.model_fn()
    rows = []

    def trace(p, g):
        g = init_states(p["init"], g)
        for r, (upd, rp) in enumerate(zip(gnn.updates, p["gnn"]["rounds"])):
            for ns, convs in upd.describe_dispatch(rp, g).items():
                rows.extend((r, ns, es, d) for es, d in convs.items())
            g = upd(rp, g)
        return g.node_sets["paper"][HIDDEN_STATE]

    with dispatch.layout(sorted_by_target=True):
        jax.eval_shape(trace, params, graph)
    return rows


def train(setup: Setup, steps: int, *, kernels: bool, provider=None,
          trainer=None) -> Run:
    """`Trainer.fit` for `steps` steps with the kernels toggle set
    (dispatch reads it while the step is traced, so each call builds and
    compiles its own step)."""
    provider = provider or setup.provider()
    timed = _Timed(provider)
    prev = dispatch.enabled()
    dispatch.enable(kernels)
    try:
        result = (trainer or setup.trainer(steps)).fit(
            setup.model_fn, setup.task, timed)
        params = result.metrics["params"]
        decisions = (dispatch_decisions(setup, params, timed.first_batch)
                     if kernels else [])
    finally:
        dispatch.enable(prev)
        provider.close()
    losses = result.metrics["train_losses"]
    check(len(losses) == steps, f"ran {len(losses)} steps, not {steps}")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    return Run(losses, timed.step_seconds, params, decisions)


def _report(name: str, run: Run) -> None:
    secs = run.step_seconds
    print(f"{name}: losses {run.losses}", flush=True)
    print(f"{name}: first step incl. compile {secs[0]:.2f} s, later steps "
          f"{[round(s, 3) for s in secs[1:]]} s (host clock, sampling "
          "excluded; one bring-up run, not a measurement)", flush=True)


def _check_losses(name: str, run: Run, ref: Run, ref_name: str) -> None:
    """Fails unless `run`'s losses agree with `ref`'s within LOSS_RTOL
    (both ran the same number of steps: `train` checks it)."""
    losses, want = np.asarray(run.losses), np.asarray(ref.losses)
    rel = np.abs(losses - want) / np.abs(want)
    print(f"{name} vs {ref_name}: max relative loss difference "
          f"{rel.max():.3e} (tolerance {LOSS_RTOL:.0e})", flush=True)
    check(bool(np.all(rel <= LOSS_RTOL)),
          f"{name} losses {run.losses} differ from the {ref_name}'s "
          f"{ref.losses} beyond rtol {LOSS_RTOL}")


def phase_train_reference(setup: Setup, steps: int = TRAIN_STEPS) -> Run:
    run = train(setup, steps, kernels=False)
    _report("train[reference]", run)
    return run


def phase_train_kernels(setup: Setup, ref: Run,
                        steps: int = TRAIN_STEPS) -> Run:
    """The reference run's steps with the Pallas kernels on.  Returns the
    run; its decisions say which kernel ran where, and in which mode."""
    run = train(setup, steps, kernels=True)
    for r, ns, es, d in run.decisions:
        what = ("none reported" if d is None else
                f"kernel {d.variant} e_block={d.e_block} "
                f"interpret={d.interpret}" if d.use_kernel else "fallback")
        print(f"dispatch round {r} {ns} <- {es}: {what}"
              f"{'' if d is None else ' | ' + d.reason}", flush=True)
    _report("train[kernels]", run)
    check(any(d is not None and d.use_kernel for *_, d in run.decisions),
          "no kernel ran on the kernel path")
    _check_losses("train[kernels]", run, ref, "reference path")
    return run


def phase_sampler_fleet(setup: Setup, ref: Run, steps: int = FLEET_STEPS,
                        num_workers: int = 2) -> Run:
    """The reference run's first steps, fed by forked sampler workers."""
    from repro.sampling_service import SamplingService
    svc = SamplingService(setup.store, setup.spec, setup.roots,
                          batch_size=setup.roots_per_step, sizes=setup.sizes,
                          num_workers=num_workers, seed=setup.seed,
                          base_seed=setup.seed, backend="process")
    run = train(setup, steps, kernels=False,
                provider=ServiceProvider(svc, own=True))
    _report("train[sampler fleet]", run)
    check(run.losses == ref.losses[:steps],
          f"sampler-fleet losses {run.losses} != in-process "
          f"{ref.losses[:steps]}")
    print(f"train[sampler fleet]: {num_workers} forked workers, losses "
          f"equal the in-process run's first {steps}", flush=True)
    return run


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def phase_serve(setup: Setup, params, n_requests: int = SERVE_REQUESTS,
                max_batch: int = 8) -> dict:
    """A warmed GNNServer (kernels on) answers `n_requests` seeded roots;
    each answer is checked against a direct forward of its subgraph."""
    from repro.serve import GNNServer
    init_states, gnn = setup.model_fn()
    task = setup.task

    def apply_fn(p, graph):
        return task.predict(p["head"],
                            gnn(p["gnn"], init_states(p["init"], graph)))

    n_papers = setup.store.num_nodes["paper"]
    roots = np.random.default_rng(setup.seed + 1).choice(
        n_papers, n_requests, replace=False)
    prev = dispatch.enabled()
    dispatch.enable(True)
    try:
        t0 = time.perf_counter()
        server = GNNServer(setup.store, setup.spec, apply_fn, params,
                           feature_dim=setup.dim, max_batch=max_batch,
                           base_seed=setup.seed, warmup_root=int(roots[0]))
        warm_s = time.perf_counter() - t0
        with server:
            served = server.serve_sync([int(r) for r in roots],
                                       timeout=600.0)
            stats = server.stats
        direct = jax.jit(apply_fn)
        sizes = server.ladder.sizes[server.ladder.rungs[0]]
        want = np.stack([
            np.asarray(direct(params, merge_and_pad(
                [sample_subgraph(setup.store, setup.spec, int(r),
                                 seed_rng(setup.seed, int(r)))], sizes)))[0]
            for r in roots])
    finally:
        dispatch.enable(prev)
    diff = float(np.max(np.abs(served - want)))
    print(f"serve: rungs {server.ladder.rungs} warmed in {warm_s:.1f} s; "
          f"{stats.served}/{n_requests} served in {stats.batches} batches "
          f"{dict(stats.batch_sizes)}, failed {stats.failed}, steady-state "
          f"recompiles {stats.steady_state_recompiles}; max |served - "
          f"direct| {diff:.3e} (tolerance {SERVE_ATOL:.0e} + "
          f"{SERVE_RTOL:.0e} x |direct|)", flush=True)
    check(stats.served == n_requests and stats.failed == 0,
          f"served {stats.served}, failed {stats.failed}")
    check(stats.steady_state_recompiles == 0,
          f"{stats.steady_state_recompiles} recompiles after warm-up")
    check(served.shape == (n_requests, setup.n_classes)
          and bool(np.all(np.isfinite(served))),
          f"served rows of shape {served.shape} or non-finite")
    check(bool(np.all(np.abs(served - want)
                      <= SERVE_ATOL + SERVE_RTOL * np.abs(want))),
          f"served answers differ from direct forwards by up to {diff}")
    return {"served": stats.served, "max_abs_diff": diff,
            "recompiles": stats.steady_state_recompiles}


# ---------------------------------------------------------------------------
# Mesh path (four chips)
# ---------------------------------------------------------------------------

def phase_mesh(setup: Setup, num_devices: int = 4,
               steps: int = FLEET_STEPS) -> dict:
    """The trainer over a num_devices x 1 and a (num_devices/2) x 2 mesh
    against a 1-device run of the same steps; the batch and the trained
    parameters must sit on `num_devices` distinct devices.

    All three run with fp32 matmuls.  At the TPU's default precision an
    f32 matmul rounds its operands to bf16, and the 2-D mesh's backward
    rounds each model shard's partial cotangent on its own before the
    shards are summed, so its losses drift from the 1-device run's by
    ~1e-3 relative within three steps whatever the partitioning's
    correctness."""
    with jax.default_matmul_precision("highest"):
        return _mesh_runs(setup, num_devices, steps)


def _mesh_runs(setup: Setup, num_devices: int, steps: int) -> dict:
    from repro.distributed import partition
    one = train(setup, steps, kernels=False)
    _report("mesh[1 device]", one)
    out = {"1": one.losses}
    for mp in (1, 2):
        rep = num_devices // mp
        plan = setup.plan(num_replicas=rep)
        sizes = tight_sizes(setup.store, setup.spec, setup.roots, plan,
                            setup.seed)
        name = f"mesh[{rep}x{mp}]"
        # the plan's own placement of the first super-batch
        mesh_plan = partition.make_plan(num_devices, model_parallel=mp)
        probe = setup.provider(sizes, rep)
        graph = next(iter(probe.epoch(0)))
        probe.close()
        placed, _ = mesh_plan.put_super_batch(
            graph, setup.task.labels(graph))
        feat = placed.node_sets["paper"]["feat"]
        shards = feat.addressable_shards
        check(len({s.device for s in shards}) == num_devices
              and len({str(s.index) for s in shards}) == num_devices,
              f"{name}: paper features on devices "
              f"{[(s.device, s.index) for s in shards]}")
        run = train(setup, steps, kernels=False,
                    provider=setup.provider(sizes, rep),
                    trainer=setup.trainer(steps, num_devices=num_devices,
                                          model_parallel=mp))
        _report(name, run)
        for leaf in jax.tree_util.tree_leaves(run.params):
            check(len(leaf.sharding.device_set) == num_devices,
                  f"{name}: a parameter sits on "
                  f"{len(leaf.sharding.device_set)} device(s)")
        print(f"{name}: batch and parameters on {num_devices} distinct "
              f"devices {sorted(d.id for d in feat.sharding.device_set)}",
              flush=True)
        _check_losses(name, run, one, "1-device run")
        out[f"{rep}x{mp}"] = run.losses
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh trainers and their "
                         "1-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{device['platform']!r}); nothing was run")
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{device['count']} device(s)")
    from repro.runtime import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    setup = build_setup(seed=args.seed, dim=HIDDEN, rounds=ROUNDS,
                        roots_per_step=ROOTS_PER_STEP, steps=TRAIN_STEPS,
                        **MAG)
    if args.chips == 4:
        phase_mesh(setup, num_devices=4)
    else:
        ref = phase_train_reference(setup)
        kernels = phase_train_kernels(setup, ref)
        interpreted = [(r, ns, es) for r, ns, es, d in kernels.decisions
                       if d is not None and d.use_kernel and d.interpret]
        check(not interpreted,
              f"kernels ran in interpret mode on the chip: {interpreted}")
        phase_sampler_fleet(setup, ref)
        phase_serve(setup, ref.params)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
