"""The dataset cache gives back what building gives."""
import numpy as np

from bench.harness import dataset
from bench.harness.program import sampling_spec
from bench.tests.conftest import load


def _same_graph(a, b):
    assert sorted(a.node_sets) == sorted(b.node_sets)
    for n in a.node_sets:
        x, y = a.node_sets[n], b.node_sets[n]
        assert x.capacity == y.capacity
        np.testing.assert_array_equal(x.sizes, y.sizes)
        assert sorted(x.features) == sorted(y.features)
        for k in x.features:
            np.testing.assert_array_equal(x.features[k], y.features[k])
    assert sorted(a.edge_sets) == sorted(b.edge_sets)
    for n in a.edge_sets:
        x, y = a.edge_sets[n], b.edge_sets[n]
        assert x.capacity == y.capacity
        np.testing.assert_array_equal(x.sizes, y.sizes)
        np.testing.assert_array_equal(x.adjacency.source, y.adjacency.source)
        np.testing.assert_array_equal(x.adjacency.target, y.adjacency.target)


def test_store_and_pool_round_trip(tmp_path):
    cfg = load("configs", "tiny_rgcn")
    built, was_built = dataset.load_store(cfg["dataset"], tmp_path)
    loaded, again = dataset.load_store(cfg["dataset"], tmp_path)
    assert was_built and not again
    fresh = dataset.build_store(cfg["dataset"])
    for store in (built, loaded):
        assert store.num_nodes == fresh.num_nodes
        for name, (src, tgt) in fresh.edges.items():
            np.testing.assert_array_equal(store.edges[name][0], src)
            np.testing.assert_array_equal(store.edges[name][1], tgt)
        for ns, feats in fresh.node_features.items():
            for k, v in feats.items():
                np.testing.assert_array_equal(store.node_features[ns][k], v)

    spec = sampling_spec(loaded.schema, cfg["sampling"])
    roots = np.arange(0, 40, 3)
    key = dataset.cache_key(cfg["dataset"])
    pool, pool_built = dataset.load_pool(built, spec, roots, 7, key,
                                         tmp_path)
    cached, cached_built = dataset.load_pool(loaded, spec, roots, 7, key,
                                             tmp_path)
    assert pool_built and not cached_built
    assert len(pool) == len(cached) == len(roots)
    for a, b in zip(pool, cached):
        _same_graph(a, b)


def test_key_follows_every_parameter():
    cfg = load("configs", "tiny_mpnn")["dataset"]
    other = dict(cfg, params=dict(cfg["params"], n_papers=601))
    assert dataset.cache_key(cfg) != dataset.cache_key(other)
    assert dataset.cache_key(cfg) != dataset.cache_key(dict(cfg, seed=1))
