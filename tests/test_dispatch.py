"""Dispatch-layer parity: for every segment op routed through
repro.kernels.dispatch, the Pallas kernel path (interpret mode on CPU)
must match the jnp reference across dtypes (fp32/bf16), feature ranks
(1-D/2-D/3-D) and padding-heavy graphs — plus unit tests for the
eligibility rules themselves."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops
from repro.core.graph_tensor import SOURCE, TARGET
from repro.kernels import dispatch

from conftest import make_graph


@contextlib.contextmanager
def kernels_on():
    ops.use_kernels(True)
    try:
        yield
    finally:
        ops.use_kernels(False)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


def padded_graph(dtype=jnp.float32):
    """Padding-heavy: ~half the users/items/edges are padding."""
    g = make_graph(n_users=5, n_items=7, n_purchased=11, pad_users=5,
                   pad_items=6, pad_edges=9, seed=3)
    g = jax.tree_util.tree_map(jnp.asarray, g)
    feats = {ns: {k: (v.astype(dtype)
                      if jnp.issubdtype(v.dtype, jnp.floating) else v)
                  for k, v in g.node_sets[ns].features.items()}
             for ns in g.node_sets}
    return g.replace_features(node_sets=feats)


def edge_values(g, shape_tail, dtype, seed=0):
    ne = g.edge_sets["purchased"].capacity
    vals = jax.random.normal(jax.random.PRNGKey(seed), (ne,) + shape_tail)
    return vals.astype(dtype)


RANKS = [(), (8,), (2, 4)]  # 1-D, 2-D, 3-D edge features
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "min"])
@pytest.mark.parametrize("tail", RANKS, ids=["1d", "2d", "3d"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_pool_edges_to_node_parity(reduce, tail, dtype):
    g = padded_graph(dtype)
    vals = edge_values(g, tail, dtype)
    base = ops.pool_edges_to_node(g, "purchased", TARGET, reduce,
                                  feature_value=vals)
    with kernels_on():
        fused = ops.pool_edges_to_node(g, "purchased", TARGET, reduce,
                                       feature_value=vals)
    assert fused.shape == base.shape and fused.dtype == base.dtype
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(base, np.float32), **tol(dtype))


@pytest.mark.parametrize("tail", [(), (4,)], ids=["1d", "2d"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_segment_softmax_parity(tail, dtype):
    g = padded_graph(dtype)
    scores = edge_values(g, tail, dtype, seed=1)
    base = ops.segment_softmax(g, "purchased", TARGET, feature_value=scores)
    with kernels_on():
        fused = ops.segment_softmax(g, "purchased", TARGET,
                                    feature_value=scores)
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(base, np.float32), **tol(dtype))
    # valid-edge coefficients sum to 1 per receiver with valid edges
    emask = np.asarray(g.edge_sets["purchased"].mask())
    assert np.all(np.asarray(fused)[~emask] == 0)


@pytest.mark.parametrize("op,set_name", [
    (ops.pool_nodes_to_context, "users"),
    (ops.pool_edges_to_context, "purchased"),
], ids=["nodes", "edges"])
@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "min"])
def test_pool_to_context_parity(op, set_name, reduce):
    from repro.data.batching import merge_graphs
    merged = merge_graphs([make_graph(seed=i) for i in range(3)])
    g = jax.tree_util.tree_map(jnp.asarray, merged)
    kwargs = (dict(feature_name="h") if set_name == "users"
              else dict(feature_value=edge_values(g, (8,), jnp.float32)))
    base = op(g, set_name, reduce, **kwargs)
    with kernels_on():
        fused = op(g, set_name, reduce, **kwargs)
    assert base.shape[0] == g.num_components
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tag", [SOURCE, TARGET])
def test_node_degree_parity(tag):
    g = padded_graph()
    base = ops.node_degree(g, "purchased", tag)
    with kernels_on():
        fused = ops.node_degree(g, "purchased", tag)
    assert fused.dtype == base.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(base))
    n_valid_edges = int(np.asarray(g.edge_sets["purchased"].mask()).sum())
    assert int(np.asarray(fused).sum()) == n_valid_edges


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_simple_conv_fused_parity(dtype):
    from repro.core.convolutions import SimpleConv
    from repro.nn.module import split_params
    g = padded_graph(dtype)
    conv = SimpleConv(16, 8 + 8, receiver_tag=TARGET,
                      sender_node_feature="h", receiver_feature="h")
    params, _ = split_params(conv.init(jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    base = conv(params, g, "purchased")
    with kernels_on():
        assert conv.fused_decision(params, g, "purchased").use_kernel
        fused = conv(params, g, "purchased")
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(base, np.float32), **tol(dtype))


def test_graph_update_round_parity_and_describe():
    """A whole vanilla-MPNN round fused vs generic, plus describe_dispatch."""
    from repro.core.graph_tensor import HIDDEN_STATE
    from repro.core.models import vanilla_mpnn
    from repro.nn.module import split_params
    g = padded_graph()
    states = {ns: {HIDDEN_STATE: g.node_sets[ns]["h"]}
              for ns in ("users", "items")}
    g = g.replace_features(node_sets=states)
    gnn = vanilla_mpnn({"purchased": ("items", "users")},
                       {"users": 8, "items": 8}, message_dim=16,
                       hidden_dim=8, num_rounds=1,
                       skip_node_sets=["items"])
    params, _ = split_params(gnn.init(jax.random.PRNGKey(0)))
    base = gnn(params, g)
    with kernels_on():
        fused = gnn(params, g)
        desc = gnn.updates[0].describe_dispatch(params["rounds"][0], g)
        decision = desc["users"]["purchased"]
        assert decision.use_kernel, decision.reason
    np.testing.assert_allclose(
        np.asarray(fused.node_sets["users"][HIDDEN_STATE]),
        np.asarray(base.node_sets["users"][HIDDEN_STATE]),
        rtol=1e-4, atol=1e-4)


def test_describe_dispatch_reports_the_pool_of_an_unfused_conv():
    """A conv the fused kernel cannot take reports the decision of the
    pool its generic path runs, and says why it was not fused."""
    from repro.core.convolutions import SimpleConv
    from repro.nn.module import split_params
    g = padded_graph()
    conv = SimpleConv(16, 8 + 8, reduce_type="max", receiver_tag=TARGET,
                      sender_node_feature="h", receiver_feature="h")
    params, _ = split_params(conv.init(jax.random.PRNGKey(0)))
    adj = g.edge_sets["purchased"].adjacency
    assert not conv.describe_dispatch(params, g, "purchased").use_kernel
    with kernels_on():
        dec = conv.describe_dispatch(params, g, "purchased")
        pool = dispatch.segment_reduce_decision(
            (adj.source.shape[0], 16), jnp.float32,
            g.node_sets[adj.target_name].capacity, "max")
    assert dec.use_kernel and pool.use_kernel
    assert (dec.variant, dec.e_block, dec.interpret) == \
        (pool.variant, pool.e_block, pool.interpret)
    assert "max pooling not fused" in dec.reason
    assert pool.reason in dec.reason


# ---------------------------------------------------------------------------
# Eligibility rules
# ---------------------------------------------------------------------------

def test_decision_disabled_routes_to_reference():
    dec = dispatch.segment_reduce_decision((100, 8), jnp.float32, 16)
    assert not dec.use_kernel and "disabled" in dec.reason


def test_decision_eligibility_rules():
    with kernels_on():
        ok = dispatch.segment_reduce_decision((1000, 64), jnp.float32, 256)
        assert ok.use_kernel and ok.interpret  # CPU -> interpret mode
        assert dispatch.MIN_E_BLOCK <= ok.e_block <= dispatch.MAX_E_BLOCK
        assert ok.e_block & (ok.e_block - 1) == 0  # power of two
        too_many = dispatch.segment_reduce_decision(
            (10, 8), jnp.float32, dispatch.MAX_SEGMENTS + 1)
        assert not too_many.use_kernel
        too_wide = dispatch.segment_reduce_decision(
            (10, dispatch.MAX_FEATURE_DIM + 1), jnp.float32, 16)
        assert not too_wide.use_kernel
        # integers always fall back: fp32 accumulation cannot guarantee
        # exact sums for arbitrary magnitudes
        int_sum = dispatch.segment_reduce_decision((10, 8), jnp.int32, 16,
                                                   "sum")
        assert not int_sum.use_kernel
        # one-hot max materialises [E_blk, N, D] and stops fitting at the
        # largest envelope shape — the CSR-run variant has no N term per
        # edge and takes over as the VMEM fallback
        assert dispatch.choose_e_block(4096, 256, reduce="max") == 0
        vmem = dispatch.segment_reduce_decision(
            (10_000, 256), jnp.float32, 4096, "max")
        assert vmem.use_kernel and vmem.variant == "runs"
        assert "runs" in vmem.reason


def test_empty_inputs_route_to_reference():
    """E=0 cannot run a Pallas grid; both entries must fall back."""
    with kernels_on():
        dec = dispatch.segment_reduce_decision((0, 8), jnp.float32, 16)
        assert not dec.use_kernel
        out = dispatch.segment_reduce(jnp.zeros((0, 8)),
                                      jnp.zeros((0,), jnp.int32), 16)
        assert out.shape == (16, 8) and not np.asarray(out).any()
        mdec = dispatch.edge_mpnn_decision(8, 8, 4, 4, 4, jnp.float32,
                                           "relu", n_edges=0)
        assert not mdec.use_kernel


def test_mixed_state_dtypes_fall_back():
    from repro.core.convolutions import SimpleConv
    from repro.nn.module import split_params
    g = padded_graph()
    items = dict(g.node_sets["items"].features)
    items["h"] = items["h"].astype(jnp.bfloat16)
    g = g.replace_features(node_sets={"items": items})
    conv = SimpleConv(16, 8 + 8, receiver_tag=TARGET,
                      sender_node_feature="h", receiver_feature="h")
    params, _ = split_params(conv.init(jax.random.PRNGKey(0)))
    with kernels_on():
        dec = conv.fused_decision(params, g, "purchased")
        assert not dec.use_kernel and "dtype" in dec.reason
        conv(params, g, "purchased")  # generic path still works


def test_kernel_e_block_heuristic_respects_reduce():
    """segment_pool(e_block=None) must size max/min blocks by the max
    formula (the [E_blk, N, D] broadcast), not the sum formula."""
    sum_block = dispatch.choose_e_block(512, 64, reduce="sum")
    max_block = dispatch.choose_e_block(512, 64, reduce="max")
    assert max_block < sum_block
    from repro.kernels.segment_pool.kernel import segment_pool
    vals = jax.random.normal(jax.random.PRNGKey(0), (100, 64))
    segs = jax.random.randint(jax.random.PRNGKey(1), (100,), 0, 512)
    out = segment_pool(vals, segs, n_segments=512, reduce="max",
                       interpret=True)  # e_block=None -> heuristic
    ref = dispatch.segment_pool_ref(vals, segs, n_segments=512,
                                    reduce="max")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_choose_e_block_scales_with_capacity():
    small = dispatch.choose_e_block(64, 16)
    large = dispatch.choose_e_block(4096, 256)
    assert small >= large > 0
    assert dispatch.choose_e_block(64, 16, n_edges=100) <= 128


def test_registry_contents():
    reg = dispatch.registry()
    assert set(reg) >= {"segment_pool", "edge_mpnn", "graph_attention"}
    for entry in reg.values():
        assert callable(entry.kernel) and callable(entry.reference)


# ---------------------------------------------------------------------------
# Layout hint and variant choice
# ---------------------------------------------------------------------------

def test_layout_context_steers_variant_choice():
    """The ambient layout() hint (BatchPlan.edges_sorted_by_target at
    trace time) flips the preferred variant; explicit sorted_ids wins
    over the context."""
    shape, n = (1000, 64), 256
    with kernels_on():
        default = dispatch.segment_reduce_decision(shape, jnp.float32, n)
        assert default.use_kernel and default.variant == "onehot"
        assert "[unsorted]" in default.reason
        with dispatch.layout(sorted_by_target=True):
            hinted = dispatch.segment_reduce_decision(shape, jnp.float32, n)
            assert hinted.use_kernel and hinted.variant == "runs"
            assert "[sorted]" in hinted.reason
            # explicit argument overrides the ambient context
            explicit = dispatch.segment_reduce_decision(
                shape, jnp.float32, n, sorted_ids=False)
            assert explicit.variant == "onehot"
        assert not dispatch.layout_sorted_by_target()  # restored


def test_layout_hint_is_performance_only():
    """A WRONG layout hint (claiming unsorted ids are sorted) still
    produces exact results — the run-scan kernel handles any id order."""
    rng = np.random.default_rng(7)
    vals = jnp.asarray(rng.standard_normal((300, 16)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, 40, 300).astype(np.int32))
    ref = dispatch.segment_pool_ref(vals, segs, n_segments=40, reduce="sum")
    with kernels_on(), dispatch.layout(sorted_by_target=True):
        dec = dispatch.segment_reduce_decision(vals.shape, vals.dtype, 40)
        assert dec.variant == "runs"  # lied about the layout
        out = dispatch.segment_reduce(vals, segs, 40, "sum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_mpnn_layout_context_steers_variant_choice():
    with kernels_on():
        base = dispatch.edge_mpnn_decision(512, 512, 32, 32, 64,
                                           jnp.float32, "relu",
                                           n_edges=2048)
        assert base.use_kernel and base.variant == "onehot"
        with dispatch.layout(sorted_by_target=True):
            hinted = dispatch.edge_mpnn_decision(512, 512, 32, 32, 64,
                                                 jnp.float32, "relu",
                                                 n_edges=2048)
            assert hinted.use_kernel and hinted.variant == "runs"


# ---------------------------------------------------------------------------
# Autotune cache consultation
# ---------------------------------------------------------------------------

def test_autotune_cache_overrides_heuristic(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setattr(autotune, "DEFAULT_CACHE_PATH",
                        tmp_path / "autotune_cache.json")
    autotune._LOADED.clear()
    rec = autotune.tune_segment_pool(64, 16, reduce="sum", sorted_ids=True,
                                     n_edges=256, iters=1)
    assert rec["variant"] in ("onehot", "runs") and rec["e_block"] > 0
    key = autotune.cache_key(
        "segment_pool", n=64, d=16, dtype="float32", reduce="sum",
        layout="sorted", backend=jax.default_backend())
    assert autotune.lookup(key) == rec
    with kernels_on():
        dispatch.use_autotune(True)
        try:
            with dispatch.layout(sorted_by_target=True):
                dec = dispatch.segment_reduce_decision(
                    (256, 16), jnp.float32, 64)
            assert dec.use_kernel
            assert dec.reason.startswith("autotuned:")
            assert dec.variant == rec["variant"]
            assert dec.e_block == rec["e_block"]
        finally:
            dispatch.use_autotune(False)
    # off by default: the same decision without autotune is heuristic
    with kernels_on(), dispatch.layout(sorted_by_target=True):
        dec = dispatch.segment_reduce_decision((256, 16), jnp.float32, 64)
        assert dec.reason.startswith("kernel:")
    autotune._LOADED.clear()


def test_autotune_rejects_stale_e_block(tmp_path, monkeypatch):
    """A cached e_block the current budget model no longer allows is
    ignored (self-invalidation on budget-model change)."""
    from repro.kernels import autotune
    monkeypatch.setattr(autotune, "DEFAULT_CACHE_PATH",
                        tmp_path / "autotune_cache.json")
    autotune._LOADED.clear()
    key = autotune.cache_key(
        "segment_pool", n=64, d=16, dtype="float32", reduce="sum",
        layout="sorted", backend=jax.default_backend())
    autotune._store(key, {"variant": "onehot", "e_block": 1 << 20,
                          "us": 1.0}, None)
    with kernels_on():
        dispatch.use_autotune(True)
        try:
            with dispatch.layout(sorted_by_target=True):
                dec = dispatch.segment_reduce_decision(
                    (256, 16), jnp.float32, 64)
            assert dec.use_kernel and dec.reason.startswith("kernel:")
        finally:
            dispatch.use_autotune(False)
    autotune._LOADED.clear()


# ---------------------------------------------------------------------------
# graph_attention (flash-backed dense within-component attention)
# ---------------------------------------------------------------------------

def _component_segments(sizes, capacity):
    comp = np.repeat(np.arange(len(sizes)), sizes)
    pad = np.full(capacity - len(comp), len(sizes))
    return jnp.asarray(np.concatenate([comp, pad]).astype(np.int32))


def test_graph_attention_parity_and_padding():
    rng = np.random.default_rng(0)
    sizes, cap = [5, 3, 9], 24  # 7 padding rows
    q, k, v = (jnp.asarray(rng.standard_normal((cap, 2, 8))
                           .astype(np.float32)) for _ in range(3))
    segs = _component_segments(sizes, cap)
    from repro.kernels.flash_attention.ref import segment_attention_ref
    ref = segment_attention_ref(q, k, v, segs)
    with kernels_on():
        dec = dispatch.graph_attention_decision(cap, 2, 8, jnp.float32)
        assert dec.use_kernel and dec.variant == "flash"
        out = dispatch.graph_attention(q, k, v, segs)
    np.testing.assert_allclose(np.asarray(out)[:17], np.asarray(ref)[:17],
                               rtol=1e-5, atol=1e-5)


def test_graph_attention_gradient_parity():
    rng = np.random.default_rng(1)
    cap = 16
    segs = _component_segments([6, 6], cap)
    q, k, v = (jnp.asarray(rng.standard_normal((cap, 2, 4))
                           .astype(np.float32)) for _ in range(3))
    mask = (np.arange(cap) < 12).astype(np.float32)[:, None, None]

    def loss(qq, kk, vv):
        out = dispatch.graph_attention(qq, kk, vv, segs)
        return jnp.sum((out * mask) ** 2)

    base = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with kernels_on():
        fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(fused, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_graph_attention_ineligible_falls_back():
    dec = dispatch.graph_attention_decision(8, 1, 4, jnp.int32)
    assert not dec.use_kernel  # integer dtype
    with kernels_on():
        toobig = dispatch.graph_attention_decision(
            dispatch.MAX_SEGMENTS + 1, 1, 4, jnp.float32)
        assert not toobig.use_kernel


@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "min"])
def test_segment_reduce_gradient_parity(reduce):
    """Kernel path is differentiable (custom VJP = reference gradients)."""
    g = padded_graph()
    vals = edge_values(g, (8,), jnp.float32)

    def loss(v):
        out = ops.pool_edges_to_node(g, "purchased", TARGET, reduce,
                                     feature_value=v)
        return jnp.sum(out ** 2)

    base = jax.grad(loss)(vals)
    with kernels_on():
        fused = jax.grad(loss)(vals)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=1e-5, atol=1e-5)


def test_fused_conv_gradient_parity():
    from repro.core.convolutions import SimpleConv
    from repro.nn.module import split_params
    g = padded_graph()
    conv = SimpleConv(16, 8 + 8, receiver_tag=TARGET,
                      sender_node_feature="h", receiver_feature="h")
    params, _ = split_params(conv.init(jax.random.PRNGKey(0)))

    def loss(p):
        return jnp.sum(conv(p, g, "purchased") ** 2)

    base = jax.grad(loss)(params)
    with kernels_on():
        fused = jax.grad(loss)(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4),
        fused, base)


def test_bf16_mean_count_does_not_saturate():
    """bf16 integers saturate at 256: the mean's count must stay fp32 so
    the kernel path's fp32-exact sum is divided by the true row count.
    (The jnp reference path still saturates the *sum* itself — a known
    bf16 limitation the kernel's fp32 accumulator exists to fix.)"""
    vals = jnp.ones((400, 2), jnp.bfloat16)
    seg = jnp.zeros((400,), jnp.int32)
    with kernels_on():
        out = dispatch.segment_reduce(vals, seg, 1, "mean")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0)


def test_segment_count_matches_bincount():
    seg = jnp.asarray([0, 0, 1, 3, 3, 3, 7, 9])  # 7/9 >= n -> padding
    cnt = dispatch.segment_count(seg, 5)
    np.testing.assert_array_equal(np.asarray(cnt), [2, 1, 0, 3, 0])


def test_segment_reduce_empty_segment_yields_zero():
    vals = jnp.ones((4, 3))
    seg = jnp.asarray([0, 0, 5, 5])  # ids >= n_segments are padding
    for reduce in ("sum", "mean", "max", "min"):
        with kernels_on():
            out = dispatch.segment_reduce(vals, seg, 3, reduce)
        np.testing.assert_array_equal(np.asarray(out[1]), 0)
        np.testing.assert_array_equal(np.asarray(out[2]), 0)


def test_worst_case_envelopes_are_dispatchable():
    """Every declared envelope corner (WORST_CASE_ENVELOPES) must yield a
    non-zero block from the kernel's own choose function — the dynamic
    twin of repro-lint rule PAL002, so the table can't drift from the
    budget model without a test telling you which side moved."""
    assert dispatch.WORST_CASE_ENVELOPES, "envelope table must not be empty"
    choosers = {"segment_pool": dispatch.choose_e_block,
                "edge_mpnn": dispatch.choose_mpnn_e_block,
                "graph_attention": dispatch.choose_attention_block}
    registered = set(dispatch.registry())
    for key, params in dispatch.WORST_CASE_ENVELOPES.items():
        kernel = key.split(":", 1)[0]
        assert kernel in registered, f"stale envelope key {key!r}"
        block = choosers[kernel](**params)
        assert block > 0, (f"envelope {key!r} ({params}) exceeds the VMEM "
                           f"budget — the kernel could never dispatch at "
                           f"its declared worst case")
