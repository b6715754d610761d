"""Out-of-core dial-in fleet: workers that connect over TCP knowing only
(service address, GraphDirectory path) must stream bit-identically to
the in-process GraphBatcher — at 1, 2 and 4 shards, through the
edges_sorted_by_target plan bit, and across a shard worker killed
mid-epoch (rebalance + local-mmap fallback)."""
import multiprocessing as mp

import numpy as np
import pytest

from repro.core.schema import mag_schema
from repro.data import (GraphBatcher, InMemorySampler, SamplingSpecBuilder,
                        find_size_constraints)
from repro.data.synthetic import synthetic_mag
from repro.sampling_service import SamplingService, wire
from repro.storage import (GraphShardServer, MmapGraphStore,
                           RemoteShardClient, ShardedGraphStore, ShardMap,
                           shard_bounds, write_graph)
from repro.storage.worker import dial_worker_main

pytestmark = pytest.mark.skipif(
    "fork" not in mp.get_all_start_methods(),
    reason="dial-worker tests fork real processes")


def _leaves(g):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(g)]


def assert_graphs_equal(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def problem(tmp_path_factory):
    store, _ = synthetic_mag(n_papers=240, n_authors=100, n_institutions=8,
                             n_fields=24, n_classes=8, feat_dim=16)
    b = SamplingSpecBuilder(mag_schema())
    seed_op = b.seed("paper")
    cited = seed_op.sample(6, "cites")
    cited.join([seed_op]).sample(4, "written")
    spec = seed_op.build()
    roots = list(range(64))
    graphs = InMemorySampler(store, spec, seed=0).sample(roots)
    sizes = find_size_constraints(graphs, 8)
    gdir = write_graph(store, str(tmp_path_factory.mktemp("gd") / "g"))
    return store, spec, roots, sizes, gdir


def _dial_service(spec, roots, sizes, gdir, *, num_workers, num_shards,
                  **kwargs):
    """SamplingService(backend='dial') + the worker processes it admitted.

    The service constructor blocks in admission; `on_listen` fires right
    after bind, which is where the workers get spawned and pointed at
    the published address — exactly the launcher pattern real
    out-of-core deployments use (workers may live on other hosts)."""
    ctx = mp.get_context("fork")
    procs = []

    def on_listen(address):
        for _ in range(num_workers):
            p = ctx.Process(target=dial_worker_main, args=(address, gdir),
                            daemon=True)
            p.start()
            procs.append(p)

    svc = SamplingService(None, spec, roots, batch_size=8, sizes=sizes,
                          num_workers=num_workers, num_replicas=1, seed=0,
                          backend="dial", num_shards=num_shards,
                          accept_timeout=30.0, on_listen=on_listen,
                          **kwargs)
    return svc, procs


def _reap(procs, timeout=10.0):
    for p in procs:
        p.join(timeout)
        if p.is_alive():
            p.kill()
            p.join(5.0)


# ---------------------------------------------------------------------------
# stream parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_workers,num_shards",
                         [(1, 1), (2, 1), (2, 2), (4, 4)])
def test_dial_stream_matches_batcher(problem, num_workers, num_shards):
    store, spec, roots, sizes, gdir = problem
    graphs = InMemorySampler(store, spec, seed=0).sample(roots)
    batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
    svc, procs = _dial_service(spec, roots, sizes, gdir,
                               num_workers=num_workers,
                               num_shards=num_shards)
    try:
        for epoch in (0, 1):
            got = list(svc.epoch(epoch))
            want = list(batcher.epoch(epoch))
            assert len(got) == len(want) == svc.num_steps
            for g, w in zip(got, want):
                assert_graphs_equal(g, w)
    finally:
        svc.close()
        _reap(procs)


def test_dial_sorted_plan_bit_travels(problem):
    """edges_sorted_by_target must cross the CONFIG frame: a dial fleet
    with the bit set streams identically to a local thread fleet with
    the bit set (and its batches really are target-sorted)."""
    store, spec, roots, sizes, gdir = problem
    svc, procs = _dial_service(spec, roots, sizes, gdir, num_workers=2,
                               num_shards=2, edges_sorted_by_target=True)
    try:
        got = list(svc.epoch(0))
    finally:
        svc.close()
        _reap(procs)
    with SamplingService(store, spec, roots, batch_size=8, sizes=sizes,
                         num_workers=2, num_replicas=1, seed=0,
                         backend="thread",
                         edges_sorted_by_target=True) as ref:
        want = list(ref.epoch(0))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert_graphs_equal(g, w)


def test_dial_kill_one_shard_worker_mid_epoch(problem):
    """Killing one of two shard workers mid-epoch: the coordinator
    rebalances its steps onto the survivor, whose ShardedGraphStore
    falls back to its own mmap of the SAME GraphDirectory for lookups
    the dead peer owned — the stream stays bit-identical."""
    store, spec, roots, sizes, gdir = problem
    graphs = InMemorySampler(store, spec, seed=0).sample(roots)
    batcher = GraphBatcher(graphs, 8, sizes, seed=0, num_replicas=1)
    svc, procs = _dial_service(spec, roots, sizes, gdir,
                               num_workers=2, num_shards=2)
    try:
        got = []
        for i, g in enumerate(svc.epoch(0)):
            got.append(g)
            if i == 1:
                procs[0].kill()  # shard 0's worker AND shard server die
        want = list(batcher.epoch(0))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert_graphs_equal(g, w)
    finally:
        svc.close()
        _reap(procs)


def test_dial_validates_shard_count(problem):
    store, spec, roots, sizes, gdir = problem
    with pytest.raises(ValueError, match="num_shards"):
        SamplingService(None, spec, roots, batch_size=8, sizes=sizes,
                        num_workers=2, num_replicas=1, seed=0,
                        backend="dial", num_shards=3, accept_timeout=5.0)
    with pytest.raises(ValueError, match="store"):
        SamplingService(None, spec, roots, batch_size=8, sizes=sizes,
                        num_workers=1, backend="process")


# ---------------------------------------------------------------------------
# shard plumbing (in-process)
# ---------------------------------------------------------------------------

def test_shard_map_partitions():
    sm = ShardMap({"n": 10}, 4)
    np.testing.assert_array_equal(shard_bounds(10, 4), [0, 2, 5, 7, 10])
    owners = sm.owner("n", np.arange(10))
    np.testing.assert_array_equal(owners, [0, 0, 1, 1, 1, 2, 2, 3, 3, 3])
    assert sm.node_range("n", 1) == (2, 5)


def test_shard_server_roundtrip(problem):
    store, spec, roots, sizes, gdir = problem
    local = MmapGraphStore(gdir)
    server = GraphShardServer(local)
    client = RemoteShardClient(server.address)
    try:
        nodes = np.array([3, 7, 11], np.int64)
        arrays = client.request(
            wire.NBR, {"edge_set": "cites"}, {"nodes": nodes})
        counts = arrays["counts"]
        flat = arrays["neighbors"]
        offs = np.concatenate([[0], np.cumsum(counts)])
        for i, u in enumerate(nodes):
            np.testing.assert_array_equal(flat[offs[i]:offs[i + 1]],
                                          store.neighbors("cites", int(u)))
        arrays = client.request(
            wire.FEAT, {"node_set": "paper"}, {"nodes": nodes})
        for feat, full in store.node_features["paper"].items():
            np.testing.assert_array_equal(arrays[feat],
                                          np.asarray(full)[nodes])
        assert server.requests_served == 2
    finally:
        client.close()
        server.close()


def test_sharded_store_lru_and_fallback(problem):
    store, spec, roots, sizes, gdir = problem
    server = GraphShardServer(MmapGraphStore(gdir))
    sh = ShardedGraphStore(MmapGraphStore(gdir), 0, 2, {1: server.address},
                          cache_entries=256)
    try:
        n = store.num_nodes["paper"]
        remote = np.arange(n - 8, n, dtype=np.int64)  # shard 1's range
        first = sh.neighbors_batch("cites", remote)
        hits0 = sh.stats["cache_hits"]
        again = sh.neighbors_batch("cites", remote)
        assert sh.stats["cache_hits"] >= hits0 + len(remote)  # all cached
        for a, b, u in zip(first, again, remote):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, store.neighbors("cites",
                                                             int(u)))
        # the flat hook over local, remote and cached lookups, and the
        # batched sampler through it, answer as the in-memory store
        mixed = np.concatenate([remote, np.arange(n // 2 - 20, n // 2 + 20,
                                                  dtype=np.int64)])[::-1]
        remote_before = sh.stats["remote"]
        for x, y in zip(sh.neighbors_flat("cites", mixed),
                        store.neighbors_flat("cites", mixed)):
            np.testing.assert_array_equal(x, y)
        assert sh.stats["remote"] > remote_before
        sampled = InMemorySampler(sh, spec, seed=0).sample(roots)
        want = InMemorySampler(store, spec, seed=0).sample(roots)
        for g, w in zip(sampled, want):
            assert_graphs_equal(g, w)
        # peer death -> local fallback, identical answers
        server.close()
        fresh = np.arange(n - 20, n - 8, dtype=np.int64)  # uncached
        got = sh.neighbors_batch("cites", fresh)
        for a, u in zip(got, fresh):
            np.testing.assert_array_equal(a, store.neighbors("cites",
                                                             int(u)))
        assert sh.stats["fallbacks"] > 0
    finally:
        sh.close()
        server.close()
