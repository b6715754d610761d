"""Out-of-core GraphDirectory format: write_graph/MmapGraphStore
roundtrips (incl. heterogeneous schemas with empty edge sets and
zero-degree nodes), bit-identical sampling against the in-memory store,
the edges_sorted_by_target layout bit, lazy index construction, and
VersionedGraphStore copy-on-write over memory-mapped features."""
import json
import os

import numpy as np
import pytest

from repro.core.schema import (EdgeSetSpec, FeatureSpec, GraphSchema,
                               NodeSetSpec, mag_schema)
from repro.data import InMemorySampler, SamplingSpecBuilder, \
    find_size_constraints
from repro.data.grouping import BatchPlan, build_batch
from repro.data.sampling import (GraphStore, sample_subgraph,
                                 sample_subgraphs, seed_rng)
from repro.data.synthetic import synthetic_mag
from repro.serve.cache import VersionedGraphStore
from repro.storage import (FORMAT_NAME, MmapGraphStore, graph_bytes,
                           write_graph)


def _tiny_hetero_store(*, empty_edge_set: bool = True,
                       n_a: int = 7, n_b: int = 5) -> GraphStore:
    """Two node sets, one populated edge set, one empty edge set, and a
    guaranteed zero-degree source node (n_a - 1 never appears as src)."""
    schema = GraphSchema(
        node_sets={"a": NodeSetSpec({"x": FeatureSpec("float32", (3,)),
                                     "y": FeatureSpec("int32")}),
                   "b": NodeSetSpec({"z": FeatureSpec("float32", (2,))})},
        edge_sets={"ab": EdgeSetSpec("a", "b"),
                   "ba": EdgeSetSpec("b", "a")})
    rng = np.random.default_rng(7)
    src = rng.integers(0, n_a - 1, 20)  # node n_a-1: degree 0
    tgt = rng.integers(0, n_b, 20)
    edges = {"ab": (src.astype(np.int64), tgt.astype(np.int64)),
             "ba": (np.zeros(0, np.int64), np.zeros(0, np.int64))}
    if not empty_edge_set:
        edges["ba"] = (rng.integers(0, n_b, 9).astype(np.int64),
                       rng.integers(0, n_a, 9).astype(np.int64))
    feats = {"a": {"x": rng.normal(size=(n_a, 3)).astype(np.float32),
                   "y": rng.integers(0, 9, n_a).astype(np.int32)},
             "b": {"z": rng.normal(size=(n_b, 2)).astype(np.float32)}}
    return GraphStore(schema, edges, feats, {"a": n_a, "b": n_b})


def _assert_stores_equal(a: GraphStore, b: GraphStore) -> None:
    assert a.num_nodes == dict(b.num_nodes)
    assert set(a.edges) == set(b.edges)
    for name in a.edges:
        # pair arrays are compared in the CANONICAL (CSR) order both
        # sides agree on: stable argsort by source
        for ae, be in zip(_canon(a, name), _canon(b, name)):
            np.testing.assert_array_equal(ae, np.asarray(be))
    assert set(a.node_features) == set(b.node_features)
    for ns in a.node_features:
        assert set(a.node_features[ns]) == set(b.node_features[ns])
        for feat, arr in a.node_features[ns].items():
            other = np.asarray(b.node_features[ns][feat])
            np.testing.assert_array_equal(np.asarray(arr), other)
            assert np.asarray(arr).dtype == other.dtype


def _canon(store: GraphStore, name: str):
    src, tgt = store.edges[name]
    src = np.asarray(src)
    tgt = np.asarray(tgt)
    order = np.argsort(src, kind="stable")
    return src[order], tgt[order]


# ---------------------------------------------------------------------------
# format roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("empty_edge_set", [True, False])
def test_roundtrip_hetero(tmp_path, empty_edge_set):
    store = _tiny_hetero_store(empty_edge_set=empty_edge_set)
    path = write_graph(store, str(tmp_path / "g"))
    m = MmapGraphStore(path)
    _assert_stores_equal(store, m)
    # zero-degree node and nodes of the empty edge set answer cleanly
    assert m.neighbors("ab", store.num_nodes["a"] - 1).size == 0
    if empty_edge_set:
        assert all(m.neighbors("ba", v).size == 0
                   for v in range(store.num_nodes["b"]))


def test_roundtrip_mag(tmp_path):
    store, _ = synthetic_mag(n_papers=150, n_authors=80, n_institutions=6,
                             n_fields=12, feat_dim=8, seed=3)
    m = MmapGraphStore(write_graph(store, str(tmp_path / "g")))
    _assert_stores_equal(store, m)
    assert graph_bytes(str(tmp_path / "g")) > 0


def test_meta_is_commit_marker(tmp_path):
    store = _tiny_hetero_store()
    path = write_graph(store, str(tmp_path / "g"))
    meta = json.load(open(os.path.join(path, "meta.json")))
    assert meta["format"] == FORMAT_NAME
    os.remove(os.path.join(path, "meta.json"))  # simulate aborted write
    with pytest.raises(FileNotFoundError):
        MmapGraphStore(path)


def test_sorted_by_target_bit(tmp_path):
    # targets non-decreasing in CSR order -> bit set
    schema = GraphSchema(node_sets={"n": NodeSetSpec()},
                         edge_sets={"e": EdgeSetSpec("n", "n")})
    sorted_store = GraphStore(
        schema,
        {"e": (np.array([0, 0, 1, 2]), np.array([0, 1, 1, 2]))},
        {}, {"n": 3})
    unsorted_store = GraphStore(
        schema,
        {"e": (np.array([0, 0, 1, 2]), np.array([1, 0, 2, 0]))},
        {}, {"n": 3})
    ms = MmapGraphStore(write_graph(sorted_store, str(tmp_path / "s")))
    mu = MmapGraphStore(write_graph(unsorted_store, str(tmp_path / "u")))
    assert ms.edges_sorted_by_target == {"e": True}
    assert mu.edges_sorted_by_target == {"e": False}


# ---------------------------------------------------------------------------
# lazy index
# ---------------------------------------------------------------------------

def test_lazy_index_in_memory():
    store = _tiny_hetero_store()
    assert store._index == {}  # nothing paid at construction
    store.neighbors("ab", 0)
    assert set(store._index) == {"ab"}  # only the sampled edge set


def test_mmap_reindex_is_zero_copy(tmp_path):
    store = _tiny_hetero_store()
    m = MmapGraphStore(write_graph(store, str(tmp_path / "g")))
    n0 = m.neighbors("ab", 0)
    np.testing.assert_array_equal(n0, store.neighbors("ab", 0))
    # the index's targets array IS the on-disk indices mmap
    assert m._index["ab"][2] is m._indices["ab"]
    # .edges was never materialized by pure neighbor queries
    assert m.edges._cache == {}


def test_mmap_edge_override_falls_back(tmp_path):
    m = MmapGraphStore(write_graph(_tiny_hetero_store(),
                                   str(tmp_path / "g")))
    m.edges["ab"] = (np.array([0, 1]), np.array([4, 3]))
    np.testing.assert_array_equal(m.neighbors("ab", 0), [4])
    np.testing.assert_array_equal(m.neighbors("ab", 1), [3])


# ---------------------------------------------------------------------------
# bit-identical sampling
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mag_problem(tmp_path_factory):
    store, _ = synthetic_mag(n_papers=240, n_authors=100, n_institutions=8,
                             n_fields=24, feat_dim=16, seed=0)
    b = SamplingSpecBuilder(mag_schema())
    seed_op = b.seed("paper")
    cited = seed_op.sample(6, "cites")
    cited.join([seed_op]).sample(4, "written")
    spec = seed_op.build()
    path = write_graph(store, str(tmp_path_factory.mktemp("gd") / "g"))
    return store, spec, path


def _flat(g):
    from repro.data.serialization import graph_to_flat
    return graph_to_flat(g)


def test_subgraphs_bit_identical(mag_problem):
    """The mmap store's `neighbors_flat` (page drops on or off) answers
    as the in-memory store's, and sampling through it, root by root or
    all roots in one call, gives the in-memory store's subgraphs."""
    store, spec, path = mag_problem
    every = np.arange(store.num_nodes["paper"])[::-1]
    roots = np.random.default_rng(0).permutation(32)
    for gather_chunk_rows in (None, 5):
        m = MmapGraphStore(path, gather_chunk_rows=gather_chunk_rows)
        for name in ("cites", "written"):
            for x, y in zip(m.neighbors_flat(name, every),
                            store.neighbors_flat(name, every)):
                assert x.dtype == y.dtype == np.int64
                np.testing.assert_array_equal(x, y)
        batched = sample_subgraphs(m, spec, roots,
                                   [seed_rng(0, r) for r in roots])
        for root, c in zip(roots, batched):
            a = sample_subgraph(store, spec, int(root), seed_rng(0, root))
            b = sample_subgraph(m, spec, int(root), seed_rng(0, root))
            fa = _flat(a)
            for other in (b, c):
                fb = _flat(other)
                assert fa.keys() == fb.keys()
                for k in fa:
                    np.testing.assert_array_equal(
                        np.asarray(fa[k]), np.asarray(fb[k]), err_msg=k)


def test_batches_bit_identical_with_plan_bit(mag_problem):
    """The full batch path (incl. edges_sorted_by_target=True) agrees
    between in-memory and mmap-backed sampling."""
    store, spec, path = mag_problem
    roots = list(range(48))
    ga = InMemorySampler(store, spec, seed=0).sample(roots)
    gb = InMemorySampler(MmapGraphStore(path), spec, seed=0).sample(roots)
    sizes = find_size_constraints(ga, 8)
    for sort_bit in (False, True):
        plan = BatchPlan(8, seed=0, num_replicas=2,
                         edges_sorted_by_target=sort_bit)
        ba = build_batch(ga[:8], plan, sizes)
        bb = build_batch(gb[:8], plan, sizes)
        fa, fb = _flat(ba), _flat(bb)
        for k in fa:
            np.testing.assert_array_equal(
                np.asarray(fa[k]), np.asarray(fb[k]), err_msg=k)


def test_plan_bit_sorts_targets_within_components(mag_problem):
    store, spec, path = mag_problem
    roots = list(range(16))
    graphs = InMemorySampler(store, spec, seed=0).sample(roots)
    sizes = find_size_constraints(graphs, 8)
    plan = BatchPlan(8, seed=0, num_replicas=1, edges_sorted_by_target=True)
    batch = build_batch(graphs[:8], plan, sizes)
    for name, es in batch.edge_sets.items():
        sz = np.asarray(es.sizes).reshape(-1)
        src = np.asarray(es.adjacency.source).reshape(-1)
        tgt = np.asarray(es.adjacency.target).reshape(-1)
        if int(sz.sum()) != len(src):
            continue  # dummy-slot edge sets are exempt (and unsorted)
        comp = np.repeat(np.arange(len(sz)), sz)
        # non-decreasing target id within each component
        same = comp[1:] == comp[:-1]
        assert np.all(tgt[1:][same] >= tgt[:-1][same]), name


# ---------------------------------------------------------------------------
# VersionedGraphStore over mmap
# ---------------------------------------------------------------------------

def test_versioned_wrap_cow(tmp_path):
    store = _tiny_hetero_store()
    path = write_graph(store, str(tmp_path / "g"))
    v = VersionedGraphStore.wrap(MmapGraphStore(path))
    before = np.asarray(store.node_features["a"]["x"]).copy()
    v.update_node_features("a", "x", [0, 2], 9.0)
    assert v.version == 1
    got = np.asarray(v.node_features["a"]["x"])
    assert np.all(got[0] == 9.0) and np.all(got[2] == 9.0)
    np.testing.assert_array_equal(got[1], before[1])
    # the GraphDirectory on disk is untouched (CoW, not write-through)
    reread = np.asarray(MmapGraphStore(path).node_features["a"]["x"])
    np.testing.assert_array_equal(reread, before)
    # untouched features stay memory-mapped
    assert not v.node_features["a"]["y"].flags.writeable


# The hypothesis roundtrip property lives in test_storage_property.py —
# a module-level importorskip must not skip the deterministic tests here.
