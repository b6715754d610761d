"""The training path's profiler spans, named scopes and compile counter."""
import contextlib
import dataclasses
import glob
import re

import jax
import pytest

from repro.core import HIDDEN_STATE, SOURCE, mag_schema
from repro.core.models import hgt, vanilla_mpnn
from repro.data import (GraphBatcher, InMemorySampler, SamplingSpecBuilder,
                        find_size_constraints)
from repro.data.synthetic import synthetic_mag
from repro.nn.layers import Linear
from repro.nn.module import Module
from repro.orchestration import (BatcherProvider, IteratorProvider,
                                 RootNodeMulticlassClassification,
                                 StoreProvider, Trainer)
from repro.orchestration import trainer as trainer_mod
from repro.train.optimizer import AdamW

DIM = 8
SPANS = ("repro.sample", "repro.merge_pad", "repro.labels", "repro.place",
         "repro.dispatch", "repro.readback")


@pytest.fixture(scope="module")
def problem():
    store, _ = synthetic_mag(n_papers=64, n_authors=32, n_institutions=5,
                             n_fields=10, n_classes=4, feat_dim=16)
    b = SamplingSpecBuilder(mag_schema())
    seed_op = b.seed("paper")
    seed_op.sample(4, "cites")
    spec = seed_op.build()
    roots = list(range(48))
    graphs = InMemorySampler(store, spec, seed=0).sample(roots)
    return store, spec, roots, graphs, find_size_constraints(graphs, 8)


def model_fn():
    class Init(Module):
        def __init__(self):
            self.lin = Linear(16, DIM)

        def init(self, key):
            return {"lin": self.lin.init(key)}

        def __call__(self, params, graph):
            return graph.replace_features(node_sets={
                "paper": {HIDDEN_STATE: jax.nn.relu(self.lin(
                    params["lin"], graph.node_sets["paper"]["feat"]))}})

    gnn = vanilla_mpnn({"cites": ("paper", "paper")}, {"paper": DIM},
                       message_dim=DIM, hidden_dim=DIM, num_rounds=2)
    return Init(), gnn


def hgt_model_fn():
    """`model_fn`'s initial states, then a 2-round HGT of 2 heads."""
    init, _ = model_fn()
    return init, hgt({"cites": ("paper", "paper")}, {"paper": DIM},
                     num_heads=2, per_head=DIM // 2, num_rounds=2,
                     receiver_tag=SOURCE)


def task():
    return RootNodeMulticlassClassification("paper", 4, DIM)


def trainer(**kw):
    return Trainer(learning_rate=1e-2, total_steps=50, log_every=10 ** 9,
                   eval_at="never", **kw)


@contextlib.contextmanager
def metadata_in_cache_key():
    """By default the persistent compile cache's key leaves out op_name
    metadata, so a hit could carry another build's names."""
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)


def step_hlo(problem, monkeypatch, model=model_fn) -> str:
    """The optimized HLO of the Trainer's train step on `problem`."""
    _, _, _, graphs, sizes = problem
    got = {}
    make = trainer_mod.make_graph_train_step

    def make_captured(*a, **kw):
        step = make(*a, **kw)

        def first(*args):
            got.setdefault("shapes", jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return step(*args)
        got["step"] = step
        return first

    with monkeypatch.context() as m, metadata_in_cache_key():
        m.setattr(trainer_mod, "make_graph_train_step", make_captured)
        trainer(max_steps=1).fit(model, task(),
                                 BatcherProvider(graphs, 8, sizes, seed=0))
        # the executable that ran, from JAX's in-memory cache
        return got["step"].lower(*got["shapes"]).compile().as_text()


def strip_metadata(hlo: str) -> str:
    return re.sub(r", metadata=\{[^}]*\}", "",
                  hlo.split("\nFileNames\n")[0])


def test_scopes_leave_the_optimized_step_unchanged(problem, monkeypatch):
    scoped = step_hlo(problem, monkeypatch)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        # the optimizer's scope decorates `update` at import
        m.setattr(AdamW, "update", AdamW.update.__wrapped__)
        plain = step_hlo(problem, monkeypatch)
    assert strip_metadata(scoped) == strip_metadata(plain)
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    for stack in ("jvp(init_states)/", "jvp(gnn)/round_0/paper/broadcast/",
                  "jvp(gnn)/round_1/paper/pool/",
                  "transpose(jvp(gnn))/round_1/paper/", "jvp(head)/",
                  "/optimizer/clip/", "/optimizer/update/"):
        assert any(stack in n for n in names), stack
    assert not any(re.search(r"round_0|optimizer", n) for n in
                   re.findall(r'op_name="([^"]*)"', plain))


def test_hgt_scopes_in_step_hlo(problem, monkeypatch):
    """HGT's attention (scores, joint softmax, weighted pool) and its
    relation transforms carry their own scopes, under the round's and
    the receiving set's, in both passes; the stack's first entry is the
    input adaptation."""
    names = set(re.findall(r'op_name="([^"]*)"',
                           step_hlo(problem, monkeypatch, hgt_model_fn)))
    for stack in ("jvp(gnn)/round_0/", "jvp(gnn)/round_1/paper/hgt_relation/",
                  "jvp(gnn)/round_2/paper/hgt_attention/",
                  "jvp(gnn)/round_1/paper/hgt_attention/pool/",
                  "transpose(jvp(gnn))/round_2/paper/hgt_attention/",
                  "transpose(jvp(gnn))/round_1/paper/hgt_relation/"):
        assert any(stack in n for n in names), stack
    # round_0 is the input adaptation, which attends to nothing
    assert not any(re.search(r"round_0/.*hgt_", n) for n in names)


def traced_spans(provider, log_dir, steps: int) -> list:
    """(name, start, end, (epoch, step)) of each ``repro.`` span of a
    `Trainer.fit` under the profiler, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        trainer(max_steps=steps).fit(model_fn, task(), provider)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    data = ProfileData.from_file(
        glob.glob(str(log_dir / "plugins/profile/*/*.xplane.pb"))[0])
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    (dict(e.stats)["epoch"], dict(e.stats)["step"]))
                   for plane in data.planes for line in plane.lines
                   for e in line.events if e.name.startswith("repro.")),
                  key=lambda sp: sp[1])


@pytest.mark.parametrize("kind", ["store", "batcher"])
def test_fit_spans_carry_their_step(problem, tmp_path, kind):
    store, spec, roots, graphs, sizes = problem
    if kind == "store":
        provider = StoreProvider(store, spec, roots, batch_size=8,
                                 sizes=sizes, seed=0)
        spans = SPANS
    else:
        provider = BatcherProvider(graphs, 8, sizes, seed=0)
        spans = SPANS[1:]    # pre-sampled: no sampling
    got = traced_spans(provider, tmp_path, steps=3)
    # the stream runs one batch ahead of max_steps
    assert [name for name, *_ in got if name == spans[0]] == [spans[0]] * 4
    ran = [sp for sp in got if sp[3][1] < 3]
    # one of each per step, in the order the step runs them, and every
    # span of step k ends before step k + 1's first one starts
    assert [(name, at) for name, _, _, at in ran] == [
        (name, (0, k)) for k in range(3) for name in spans]
    for a, b in zip(ran, ran[1:]):
        assert a[1] <= a[2] <= b[1]


def test_sampler_workers_open_no_span(problem, tmp_path, monkeypatch):
    """Forked sampler workers build batches without touching the
    profiler: the workers run the numpy-only data layer, and the spans
    live in the trainer process's providers."""
    import os
    from repro.sampling_service import SamplingService
    store, spec, roots, _, sizes = problem
    marks = tmp_path / "marks"
    parent = os.getpid()

    def record(*a, **kw):
        if os.getpid() != parent:
            with open(marks, "a") as f:
                f.write(f"{os.getpid()}\n")
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", record)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", record)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, backend="process") as svc:
        batches = list(svc.epoch(0))
    assert len(batches) == len(roots) // 8
    assert not marks.exists()


def test_step_compiles_counts_programs(problem):
    _, _, _, graphs, sizes = problem
    fixed = trainer(max_steps=3).fit(
        model_fn, task(), BatcherProvider(graphs, 8, sizes, seed=0))
    assert fixed.metrics["step_compiles"] == [1, 1, 1]

    wider = dataclasses.replace(
        sizes, total_num_nodes={k: v + 8 for k, v in
                                sizes.total_num_nodes.items()})

    def stream(epoch):
        small = GraphBatcher(graphs, 8, sizes, seed=0).epoch(epoch)
        yield next(small)
        yield next(small)
        yield next(GraphBatcher(graphs, 8, wider, seed=0).epoch(epoch))

    changed = trainer().fit(model_fn, task(),
                            IteratorProvider(stream, num_steps=3))
    assert changed.metrics["step_compiles"] == [1, 1, 2]
