"""Data-exchange op tests: padded index-based ops == dense-adjacency oracle,
and padding invariance (the TPU adaptation must match ragged semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops
from repro.core.graph_tensor import SOURCE, TARGET

from conftest import make_graph


def dense_pool_oracle(graph, reduce):
    """Pool purchased-edge messages (item h) to users via dense adjacency."""
    es = graph.edge_sets["purchased"]
    n_items = graph.node_sets["items"].capacity
    n_users = graph.node_sets["users"].capacity
    e_valid = int(np.asarray(es.sizes).sum())
    a = np.zeros((n_users, n_items), np.float32)
    h = np.asarray(graph.node_sets["items"]["h"])
    out = np.zeros((n_users, h.shape[1]), np.float32)
    vals = [[] for _ in range(n_users)]
    for i in range(e_valid):
        u = int(es.adjacency.target[i])
        s = int(es.adjacency.source[i])
        vals[u].append(h[s])
    for u in range(n_users):
        if not vals[u]:
            continue
        stack = np.stack(vals[u])
        if reduce == "sum":
            out[u] = stack.sum(0)
        elif reduce == "mean":
            out[u] = stack.mean(0)
        elif reduce == "max":
            out[u] = stack.max(0)
        elif reduce == "min":
            out[u] = stack.min(0)
    return out


@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "min"])
@pytest.mark.parametrize("padded", [False, True])
def test_pool_edges_to_node_matches_dense_oracle(reduce, padded):
    g = make_graph(pad_users=3 if padded else 0,
                   pad_items=2 if padded else 0,
                   pad_edges=5 if padded else 0)
    gj = jax.tree_util.tree_map(jnp.asarray, g)
    msg = ops.broadcast_node_to_edges(gj, "purchased", SOURCE,
                                      feature_name="h")
    pooled = ops.pool_edges_to_node(gj, "purchased", TARGET, reduce,
                                    feature_value=msg)
    oracle = dense_pool_oracle(g, reduce)
    np.testing.assert_allclose(np.asarray(pooled), oracle, rtol=1e-5,
                               atol=1e-5)


def test_padding_invariance():
    """Valid rows of every op must be identical with/without padding."""
    from repro.data.batching import (SizeConstraints, merge_graphs,
                                     pad_to_sizes)
    g0 = make_graph()
    g1 = pad_to_sizes(merge_graphs([g0]), SizeConstraints(
        total_num_components=2,
        total_num_nodes={"users": 9, "items": 9},
        total_num_edges={"purchased": 15, "is-friend": 11}))
    j0 = jax.tree_util.tree_map(jnp.asarray, g0)
    j1 = jax.tree_util.tree_map(jnp.asarray, g1)
    p0 = ops.pool_edges_to_node(j0, "purchased", TARGET, "sum",
                                feature_name=None,
                                feature_value=ops.broadcast_node_to_edges(
                                    j0, "purchased", SOURCE,
                                    feature_name="h"))
    p1 = ops.pool_edges_to_node(j1, "purchased", TARGET, "sum",
                                feature_value=ops.broadcast_node_to_edges(
                                    j1, "purchased", SOURCE,
                                    feature_name="h"))
    np.testing.assert_allclose(np.asarray(p0),
                               np.asarray(p1)[:g0.node_sets["users"]
                                              .capacity], rtol=1e-5)


def test_segment_softmax_sums_to_one():
    g = make_graph(pad_edges=4)
    gj = jax.tree_util.tree_map(jnp.asarray, g)
    es = gj.edge_sets["purchased"]
    scores = jnp.asarray(
        np.random.default_rng(0).normal(size=(es.capacity,)).astype(
            np.float32))
    sm = ops.segment_softmax(gj, "purchased", TARGET, feature_value=scores)
    sums = jax.ops.segment_sum(
        sm, es.adjacency.target,
        num_segments=gj.node_sets["users"].capacity)
    deg = ops.node_degree(gj, "purchased", TARGET)
    np.testing.assert_allclose(np.asarray(sums)[np.asarray(deg) > 0], 1.0,
                               rtol=1e-5)


def _old_segment_softmax(graph, name, tag, value):
    """`ops.segment_softmax` of one edge set as it was written before it
    took several: the single-set case must compute exactly this."""
    es = graph.edge_sets[name]
    idx = es.adjacency.target if tag == TARGET else es.adjacency.source
    n = graph.node_sets[es.adjacency.target_name if tag == TARGET
                        else es.adjacency.source_name].capacity
    emask = es.mask()
    emask_b = emask.reshape(emask.shape + (1,) * (value.ndim - 1))
    seg_ids = jnp.where(emask, idx, n)
    seg_max = ops._mp_segment_reduce(value, seg_ids, n, "max",
                                     sorted_ids=None if tag == TARGET
                                     else False)
    shifted = jnp.where(emask_b, value - jnp.take(seg_max, idx, axis=0),
                        -jnp.inf)
    exp = jnp.where(emask_b, jnp.exp(shifted), 0)
    seg_sum = ops._mp_segment_reduce(exp, seg_ids, n, "sum",
                                     sorted_ids=None if tag == TARGET
                                     else False)
    return exp / jnp.maximum(jnp.take(seg_sum, idx, axis=0), 1e-37)


JOINT = ("purchased", "is-friend")   # both target "users"


def _joint_case():
    g = jax.tree_util.tree_map(jnp.asarray, make_graph(pad_edges=4))
    rng = np.random.default_rng(1)
    scores = [jnp.asarray(rng.normal(size=(g.edge_sets[n].capacity, 2))
                          .astype(np.float32)) for n in JOINT]
    return g, scores


@pytest.mark.parametrize("case", ["sums_to_one", "equals_concatenated",
                                  "padding_gets_zero", "one_set_is_today",
                                  "differs_from_per_set"])
def test_joint_segment_softmax(case):
    """One softmax over every edge into a node across several edge sets
    (HGT's Eq. 3), two heads of scores."""
    g, scores = _joint_case()
    coefs = ops.segment_softmax(g, list(JOINT), TARGET,
                                feature_value=scores)
    n_users = g.node_sets["users"].capacity
    valid = [np.asarray(g.edge_sets[n].mask()) for n in JOINT]
    tgts = [np.asarray(g.edge_sets[n].adjacency.target) for n in JOINT]
    c = [np.asarray(x) for x in coefs]
    if case == "sums_to_one":
        sums = np.zeros((n_users, 2))
        for ci, t, v in zip(c, tgts, valid):
            np.add.at(sums, t[v], ci[v])
        has = np.zeros(n_users, bool)
        for t, v in zip(tgts, valid):
            has[t[v]] = True
        # node 0 and others hear over both sets in this graph
        assert has.sum() >= 3
        np.testing.assert_allclose(sums[has], 1.0, rtol=1e-6)
        np.testing.assert_array_equal(sums[~has], 0.0)
    elif case == "equals_concatenated":
        s = np.concatenate([np.asarray(x)[v] for x, v in zip(scores, valid)])
        t = np.concatenate([t[v] for t, v in zip(tgts, valid)])
        want = np.zeros_like(s)
        for u in np.unique(t):
            e = np.exp(s[t == u] - s[t == u].max(0))
            want[t == u] = e / e.sum(0)
        got = np.concatenate([ci[v] for ci, v in zip(c, valid)])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    elif case == "padding_gets_zero":
        assert (~valid[0]).sum() == 4
        np.testing.assert_array_equal(c[0][~valid[0]], 0.0)
    elif case == "one_set_is_today":
        for name, x in zip(JOINT, scores):
            one = ops.segment_softmax(g, name, TARGET, feature_value=x)
            np.testing.assert_array_equal(
                np.asarray(one),
                np.asarray(_old_segment_softmax(g, name, TARGET, x)))
            listed = ops.segment_softmax(g, [name], TARGET,
                                         feature_value=[x])
            np.testing.assert_array_equal(np.asarray(listed[0]),
                                          np.asarray(one))
    else:
        apart = [np.asarray(ops.segment_softmax(g, n, TARGET,
                                                feature_value=x))
                 for n, x in zip(JOINT, scores)]
        both = set(tgts[0][valid[0]]) & set(tgts[1][valid[1]])
        assert both, "no user hears over both edge sets"
        for ci, a, t, v in zip(c, apart, tgts, valid):
            shared = v & np.isin(t, list(both))
            assert np.all(ci[shared] < a[shared])


def test_joint_softmax_needs_one_receiver_set():
    g, scores = _joint_case()
    with pytest.raises(ValueError, match="not in one node set"):
        ops.segment_softmax(g, list(JOINT), SOURCE, feature_value=scores)


def test_context_ops_roundtrip(graph):
    total = ops.pool_nodes_to_context(graph, "users", "sum",
                                      feature_name="h")
    assert total.shape == (1, 8)
    back = ops.broadcast_context_to_nodes(graph, "users",
                                          feature_value=total)
    assert back.shape == (graph.node_sets["users"].capacity, 8)
    # paper appendix A.3: max spend / fraction pattern
    mx = ops.pool_nodes_to_context(graph, "users", "max", feature_name="h")
    assert bool(jnp.all(jnp.isfinite(mx)))


def test_graphtensor_jit_roundtrip(graph):
    @jax.jit
    def f(g):
        msg = ops.broadcast_node_to_edges(g, "purchased", SOURCE,
                                          feature_name="h")
        return ops.pool_edges_to_node(g, "purchased", TARGET, "sum",
                                      feature_value=msg)

    out = f(graph)
    assert out.shape[0] == graph.node_sets["users"].capacity


def test_replace_features(graph):
    g2 = graph.replace_features(
        node_sets={"users": {"hidden_state":
                             graph.node_sets["users"]["h"] * 2}})
    assert "hidden_state" in g2.node_sets["users"].features
    assert "h" in graph.node_sets["users"].features  # original untouched
