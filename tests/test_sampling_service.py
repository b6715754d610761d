"""Async sampling service: wire framing, stream parity with the
in-process GraphBatcher, determinism across fleet sizes, rebalance on
worker loss, prefetch semantics, and the runner's service path."""
import socket
import threading
import time

import numpy as np
import pytest

from repro.core.schema import mag_schema
from repro.data import (GraphBatcher, InMemorySampler, SamplingSpecBuilder,
                        find_size_constraints)
from repro.data.grouping import BatchPlan, build_batch
from repro.data.pipeline import prefetch
from repro.data.synthetic import synthetic_mag
from repro.sampling_service import SamplingService, wire


def _leaves(g):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(g)]


def assert_graphs_equal(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def problem():
    store, _ = synthetic_mag(n_papers=240, n_authors=100, n_institutions=8,
                             n_fields=24, n_classes=8, feat_dim=32)
    b = SamplingSpecBuilder(mag_schema())
    seed_op = b.seed("paper")
    cited = seed_op.sample(8, "cites")
    cited.join([seed_op]).sample(4, "written")
    spec = seed_op.build()
    roots = list(range(64))
    graphs = InMemorySampler(store, spec, seed=0).sample(roots)
    sizes = find_size_constraints(graphs, 8)
    return store, spec, roots, graphs, sizes


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def test_wire_roundtrip_control_and_batch(problem):
    store, spec, roots, graphs, sizes = problem
    plan = BatchPlan(8, seed=0, num_replicas=2)
    batch = build_batch(graphs[:8], plan, sizes)
    a, b = wire.socket_pair()
    try:
        wire.send_frame(a, wire.ASSIGN, {"epoch": 3, "steps": [1, 2]})
        wire.send_frame(a, wire.BATCH, {"worker": 0, "epoch": 3, "step": 1},
                        batch)
        kind, meta, g = wire.recv_frame(b)
        assert (kind, meta) == (wire.ASSIGN, {"epoch": 3, "steps": [1, 2]})
        assert g is None
        kind, meta, g = wire.recv_frame(b)
        assert kind == wire.BATCH and meta["step"] == 1
        assert_graphs_equal(g, batch)  # incl. [R, ...] stacked leaves
        assert g.node_sets["paper"].capacity == batch.node_sets[
            "paper"].capacity  # static aux survives the wire
    finally:
        a.close()
        b.close()


def test_wire_bad_magic_and_eof():
    a, b = wire.socket_pair()
    try:
        a.sendall(b"XXXX")
        with pytest.raises(wire.WireError):
            wire.recv_frame(b)
    finally:
        a.close()
        b.close()
    a, b = wire.socket_pair()
    try:
        a.close()  # clean close before any frame
        with pytest.raises(EOFError):
            wire.recv_frame(b)
    finally:
        b.close()
    a, b = wire.socket_pair()
    try:
        a.sendall(wire.MAGIC + b"\x00\x00")  # truncated mid-frame
        a.close()
        with pytest.raises(wire.WireError):
            wire.recv_frame(b)
    finally:
        b.close()


def test_wire_timeout_preserves_stream(problem):
    store, spec, roots, graphs, sizes = problem
    plan = BatchPlan(8, seed=0, num_replicas=1)
    batch = build_batch(graphs[:8], plan, sizes)
    a, b = wire.socket_pair()
    try:
        with pytest.raises(socket.timeout):
            wire.recv_frame(b, timeout=0.05)
        wire.send_frame(a, wire.BATCH, {"worker": 0, "epoch": 0, "step": 0},
                        batch)
        kind, meta, g = wire.recv_frame(b, timeout=1.0)
        assert kind == wire.BATCH
        assert_graphs_equal(g, batch)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# stream contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_workers", [1, 2, 3])
def test_stream_matches_in_process_batcher(problem, num_workers):
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 16, sizes, seed=0, num_replicas=2)
    with SamplingService(store, spec, roots, batch_size=16, sizes=sizes,
                         num_workers=num_workers, num_replicas=2,
                         seed=0, base_seed=0) as svc:
        for epoch in (0, 1):
            got = list(svc.epoch(epoch))
            want = list(batcher.epoch(epoch))
            assert len(got) == len(want) == svc.num_steps
            for g, w in zip(got, want):
                assert_graphs_equal(g, w)


@pytest.mark.parametrize("sort_bit", [True, False])
def test_stream_bit_identity_either_edge_layout(problem, sort_bit):
    """The service and the in-process batcher stay bit-identical with
    edges sorted by target (the new default) AND with the opt-out — the
    layout bit is part of the shared BatchPlan contract, not a
    service-side transform.  The batcher runs over the frozen per-root
    sampler's subgraphs, which the workers' batched sampling matches."""
    from repro.data.sampling import seed_rng
    from test_sampling import frozen_sample_subgraph
    store, spec, roots, _, sizes = problem
    graphs = [frozen_sample_subgraph(store, spec, r, seed_rng(0, r))
              for r in roots]
    batcher = GraphBatcher(graphs, 16, sizes, seed=0, num_replicas=2,
                           edges_sorted_by_target=sort_bit)
    assert batcher.plan.edges_sorted_by_target is sort_bit
    with SamplingService(store, spec, roots, batch_size=16, sizes=sizes,
                         num_workers=2, num_replicas=2, seed=0,
                         edges_sorted_by_target=sort_bit) as svc:
        got = list(svc.epoch(0))
        want = list(batcher.epoch(0))
        assert len(got) == len(want) == svc.num_steps
        for g, w in zip(got, want):
            assert_graphs_equal(g, w)


def test_sorted_layout_is_pure_edge_reorder(problem):
    """Sorted vs unsorted batches carry the SAME edge multiset per edge
    set (sorting never drops/duplicates), and the sorted stream's target
    ids are non-decreasing within each component."""
    store, spec, roots, graphs, sizes = problem
    b_sorted = GraphBatcher(graphs, 8, sizes, seed=0,
                            edges_sorted_by_target=True)
    b_unsorted = GraphBatcher(graphs, 8, sizes, seed=0,
                              edges_sorted_by_target=False)
    for gs, gu in zip(b_sorted.epoch(0), b_unsorted.epoch(0)):
        for name in gs.edge_sets:
            es, eu = gs.edge_sets[name], gu.edge_sets[name]
            pairs_s = sorted(zip(np.asarray(es.adjacency.source).tolist(),
                                 np.asarray(es.adjacency.target).tolist()))
            pairs_u = sorted(zip(np.asarray(eu.adjacency.source).tolist(),
                                 np.asarray(eu.adjacency.target).tolist()))
            assert pairs_s == pairs_u
            n_valid = int(np.asarray(es.sizes).sum())
            tgt = np.asarray(es.adjacency.target)[:n_valid]
            assert np.all(np.diff(tgt) >= 0)  # globally non-decreasing
        break  # one step is enough


def test_stream_start_step_skip(problem):
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 16, sizes, seed=0, num_replicas=2)
    with SamplingService(store, spec, roots, batch_size=16, sizes=sizes,
                         num_workers=2, num_replicas=2, seed=0) as svc:
        got = list(svc.epoch(0, start_step=2))
        want = list(batcher.epoch(0, start_step=2))
        assert len(got) == len(want) == svc.num_steps - 2
        for g, w in zip(got, want):
            assert_graphs_equal(g, w)


def test_stream_matches_batcher_with_world_sharding(problem):
    """Legacy contract (num_replicas=None) at world > 1: the service must
    pad to the same 1/world rank constraints GraphBatcher uses — the
    multi-host seam the ROADMAP items plug into."""
    store, spec, roots, graphs, sizes = problem
    # legacy mode takes the GLOBAL batch constraint and pads each rank to
    # its 1/world share, so derive sizes for the full batch of 16
    sizes16 = find_size_constraints(graphs, 16)
    for rank in (0, 1):
        batcher = GraphBatcher(graphs, 16, sizes16, seed=0, rank=rank,
                               world=2)
        with SamplingService(store, spec, roots, batch_size=16,
                             sizes=sizes16, num_workers=2, seed=0,
                             rank=rank, world=2) as svc:
            got = list(svc.epoch(0))
            want = list(batcher.epoch(0))
            assert len(got) == len(want) == svc.num_steps
            for g, w in zip(got, want):
                assert_graphs_equal(g, w)


def test_thread_backend_parity(problem):
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 16, sizes, seed=0, num_replicas=2)
    with SamplingService(store, spec, roots, batch_size=16, sizes=sizes,
                         num_workers=2, num_replicas=2, seed=0,
                         backend="thread") as svc:
        for g, w in zip(svc.epoch(0), batcher.epoch(0)):
            assert_graphs_equal(g, w)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_rebalance_on_worker_killed_before_epoch(problem):
    """A worker that dies before producing anything: every one of its
    steps must be re-executed by the survivor, stream unchanged."""
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0) as svc:
        svc.kill_worker(1)
        svc.coordinator.workers[1].process.join(5.0)
        got = list(svc.epoch(0))
        want = list(batcher.epoch(0))
        assert len(got) == len(want) == svc.num_steps
        for g, w in zip(got, want):
            assert_graphs_equal(g, w)
        assert not svc.coordinator.workers[1].alive


def test_rebalance_on_worker_killed_mid_epoch(problem):
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0) as svc:
        got = []
        for i, g in enumerate(svc.epoch(0)):
            got.append(g)
            if i == 1:
                svc.kill_worker(0)
        want = list(batcher.epoch(0))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert_graphs_equal(g, w)


def test_respawn_restores_fleet_width(problem):
    """Coordinator-driven respawn: kill a worker mid-epoch; the stream is
    unchanged AND the fleet returns to full width (a fresh process under
    the dead worker's id), with the replacement delivering batches in the
    next epoch instead of survivors absorbing its steps forever.

    The death is detected either by the client's blocked read (mid-epoch
    rebalance) or, when the worker flushed its whole stripe before dying,
    by the next epoch's assign-time sweep — so the full-width assertions
    are made after epoch 1 starts, where both paths have converged."""
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0,
                         respawn=True) as svc:
        got = []
        for i, g in enumerate(svc.epoch(0)):
            got.append(g)
            if i == 1:
                svc.kill_worker(0)
        want = list(batcher.epoch(0))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert_graphs_equal(g, w)
        # epoch 1: the replacement serves its stripe (stream still exact)
        got1 = list(svc.epoch(1))
        want1 = list(batcher.epoch(1))
        assert len(got1) == len(want1)
        for g, w in zip(got1, want1):
            assert_graphs_equal(g, w)
        # back to full width: both worker ids alive with live processes,
        # exactly one retired handle (the killed original), and the
        # replacement's watermark advanced through epoch 1
        alive = svc.coordinator.alive()
        assert len(alive) == 2
        assert all(w.process_alive() for w in alive)
        assert len(svc.coordinator.retired) == 1
        marks = svc.watermarks()
        assert marks[0] is not None and marks[0][0] == 1, marks


def test_respawn_disabled_keeps_legacy_absorb(problem):
    """Without respawn=True the PR-3 contract is unchanged: survivors
    absorb the dead worker's steps and the fleet stays narrow (the
    assign-time sweep marks the death at the latest by epoch 1)."""
    store, spec, roots, graphs, sizes = problem
    batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0) as svc:
        for i, _ in enumerate(svc.epoch(0)):
            if i == 1:
                svc.kill_worker(0)
        got1 = list(svc.epoch(1))  # survivor absorbs the whole epoch
        want1 = list(batcher.epoch(1))
        assert len(got1) == len(want1)
        for g, w in zip(got1, want1):
            assert_graphs_equal(g, w)
        assert len(svc.coordinator.alive()) == 1
        assert not svc.coordinator.workers[0].alive
        assert svc.coordinator.retired == []  # nothing replaced


def test_dead_fleet_raises(problem):
    from repro.sampling_service import DeadFleetError
    store, spec, roots, graphs, sizes = problem
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=1, num_replicas=1, seed=0) as svc:
        svc.kill_worker(0)
        svc.coordinator.workers[0].process.join(5.0)
        with pytest.raises(DeadFleetError):
            list(svc.epoch(0))


def test_watermarks_track_progress(problem):
    store, spec, roots, graphs, sizes = problem
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0) as svc:
        list(svc.epoch(0))
        marks = svc.watermarks()
        assert set(marks) == {0, 1}
        assert all(m is not None and m[0] == 0 for m in marks.values())


# ---------------------------------------------------------------------------
# prefetch (satellite: exception propagation + early-close join)
# ---------------------------------------------------------------------------

def test_prefetch_reraises_source_exception():
    def boom():
        yield 1
        yield 2
        raise RuntimeError("sampler exploded")

    it = prefetch(boom(), depth=1)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(RuntimeError, match="sampler exploded"):
        next(it)


def test_prefetch_reraises_even_when_queue_was_full():
    def boom():
        yield from range(5)
        raise ValueError("late failure behind a full queue")

    got = []
    with pytest.raises(ValueError, match="late failure"):
        for x in prefetch(iter(boom()), depth=2):
            got.append(x)
            time.sleep(0.01)  # let the producer run ahead and fill up
    assert got == list(range(5))


def test_prefetch_early_close_joins_thread():
    n_before = threading.active_count()

    def slow_source():
        for i in range(1000):
            yield i

    it = prefetch(slow_source(), depth=1)
    assert next(it) == 0
    it.close()  # must unblock the producer stuck on the full queue + join
    deadline = time.time() + 5.0
    while threading.active_count() > n_before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= n_before


def test_prefetch_order_preserved():
    assert list(prefetch(iter(range(100)), depth=3)) == list(range(100))


def test_prefetch_no_live_named_thread_after_close():
    # the thread-lifecycle contract repro-lint THR002 enforces statically,
    # checked dynamically: closing the generator (normally or early) must
    # leave no live "graph-prefetch" thread behind
    def alive():
        return [t for t in threading.enumerate()
                if t.name == "graph-prefetch" and t.is_alive()]

    list(prefetch(iter(range(10)), depth=2))  # exhausted normally
    it = prefetch(iter(range(1000)), depth=1)
    next(it)
    it.close()  # closed early, producer blocked on a full queue
    deadline = time.time() + 5.0
    while alive() and time.time() < deadline:
        time.sleep(0.01)
    assert not alive()


# ---------------------------------------------------------------------------
# runner integration
# ---------------------------------------------------------------------------

def test_runner_service_path_matches_in_process_loss(problem):
    """runner.run(sampler='service') reaches the in-process loss exactly
    (bit-identical batches => identical float trajectory)."""
    import jax
    from repro.core import HIDDEN_STATE
    from repro.core.models import vanilla_mpnn
    from repro.nn.layers import Linear
    from repro.nn.module import Module
    from repro.orchestration import RootNodeMulticlassClassification, run

    store, spec, roots, graphs, sizes = problem
    dim = 16

    class Init(Module):
        def __init__(self):
            self.paper = Linear(32, dim)

        def init(self, key):
            return {"paper": self.paper.init(key)}

        def __call__(self, params, graph):
            return graph.replace_features(node_sets={
                "paper": {HIDDEN_STATE: jax.nn.relu(self.paper(
                    params["paper"], graph.node_sets["paper"]["feat"]))}})

    gnn = vanilla_mpnn({"cites": ("paper", "paper")}, {"paper": dim},
                       message_dim=dim, hidden_dim=dim, num_rounds=2)
    task = RootNodeMulticlassClassification("paper", 8, dim)

    def labels_fn(graph):
        arr = np.asarray(graph.node_sets["paper"].sizes)
        lab = np.asarray(graph.node_sets["paper"]["labels"])
        return np.stack([task.root_labels(arr[r], lab[r])
                         for r in range(arr.shape[0])]).astype(np.int32)

    def train_batches(epoch):
        batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
        for g in batcher.epoch(epoch):
            yield g, labels_fn(g)

    kwargs = dict(model_fn=lambda: (Init(), gnn), task=task, epochs=1,
                  learning_rate=1e-3, total_steps=10, log_every=10 ** 9,
                  max_steps=3, num_devices=1)
    res_inproc = run(train_batches=train_batches, **kwargs)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0) as svc:
        res_service = run(sampler="service", service=svc,
                          label_fn=labels_fn, **kwargs)
    assert res_inproc.step == res_service.step == 3
    assert res_inproc.train_loss == res_service.train_loss


def test_runner_service_path_validates_args(problem):
    from repro.orchestration import run

    with pytest.raises(ValueError, match="service"):
        run(sampler="service", model_fn=None, task=None)
    with pytest.raises(ValueError, match="train_batches"):
        run(sampler="in_process", model_fn=None, task=None)
    with pytest.raises(ValueError, match="unknown sampler"):
        run(sampler="bogus", model_fn=None, task=None)
