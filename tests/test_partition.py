"""The unified 2-D partitioning layer (repro.distributed.partition):
MeshPlan specs, optimizer state_axes under ZeRO-1 (incl. Adafactor's
factored vr/vc leaves), psum-corrected norms, the model-shard dispatch
budget, and 8-device (data=4, model=2) loss parity vs the 1-device run."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import partition
from repro.train.optimizer import (AdamW, Adafactor, clip_by_global_norm,
                                   global_norm)

from test_graph_sharding import tiny_graph


# ---------------------------------------------------------------------------
# MeshPlan construction + graph specs
# ---------------------------------------------------------------------------

def test_make_mesh_1d_is_data_only():
    mesh = partition.make_mesh(1)
    assert mesh.axis_names == ("data",)
    plan = partition.plan_for(mesh)
    assert plan.data_size == 1 and plan.model_size == 1
    assert plan.model_axis is None and not plan.zero_enabled()


def test_make_mesh_rejects_indivisible_model_parallel():
    with pytest.raises(ValueError, match="model_parallel"):
        partition.make_mesh(1, model_parallel=2)


def test_graph_specs_1d_match_data_only_contract():
    """On a 1-D mesh the 2-D resolver reproduces the PR-2 specs exactly:
    leading group axis over "data", everything else replicated (the
    "feature" -> "model" rule drops out without a model axis)."""
    from repro.core.graph_tensor import stack_graphs
    plan = partition.plan_for(partition.make_mesh(1))
    stacked = stack_graphs([tiny_graph(0), tiny_graph(1)])
    specs = jax.tree_util.tree_leaves(
        plan.graph_specs(stacked), is_leaf=lambda s: isinstance(s, P))
    assert specs, "no spec leaves"
    for s in specs:
        ents = tuple(s)
        assert ents[0] == "data"
        assert all(e is None for e in ents[1:])


def test_leaf_axes_feature_only_on_rank3():
    assert partition._leaf_axes(np.zeros((4, 8, 16))) == \
        ("batch", None, "feature")
    assert partition._leaf_axes(np.zeros((4, 8))) == ("batch", None)
    assert partition._leaf_axes(np.zeros((4,))) == ("batch",)


def test_put_super_batch_promotes_scalar_via_plan():
    from repro.core.graph_tensor import stack_size
    plan = partition.make_plan(1)
    g, labels = plan.put_super_batch(tiny_graph(), np.zeros(2, np.int32))
    assert stack_size(g) == 1 and labels.shape == (1, 2)


# ---------------------------------------------------------------------------
# Optimizer state_axes under ZeRO (the satellite coverage): AdamW mirrors
# params; Adafactor's factored vr/vc drop the factored dims
# ---------------------------------------------------------------------------

def test_adamw_state_axes_mirror_params():
    axes = {"w": ("embed", None), "b": ("embed",)}
    st = AdamW().state_axes(axes)
    assert st.step == ()
    assert st.m == axes and st.v == axes


def test_adafactor_state_axes_factored_leaves():
    axes = {"w2": ("embed", None),            # 2-D: factored
            "w3": ("embed", None, None),      # 3-D: factored
            "b": ("embed",)}                  # 1-D: unfactored
    st = Adafactor().state_axes(axes)
    assert st.step == ()
    # vr drops the last dim's axis
    assert st.vr == {"w2": ("embed",), "w3": ("embed", None),
                     "b": ("embed",)}
    # vc drops the second-to-last dim's axis (scalar for unfactored)
    assert st.vc == {"w2": (None,), "w3": ("embed", None), "b": ()}


def test_adafactor_state_specs_resolve_against_state_shapes():
    """state_axes must resolve leaf-for-leaf against the actual factored
    state shapes (vr [rows], vc [cols]) — the ZeRO placement path."""
    plan = partition.make_plan(1)
    params = {"w2": jnp.zeros((8, 6)), "w3": jnp.zeros((4, 8, 6)),
              "b": jnp.zeros((8,))}
    opt = Adafactor()
    state = opt.init(params)
    axes = plan.param_logical_axes(params)
    specs = plan._resolve_axes_tree(opt.state_axes(axes), state)
    assert state.vr["w2"].shape == (8,) and tuple(specs.vr["w2"]) == ("data",)
    assert state.vc["w2"].shape == (6,) and tuple(specs.vc["w2"]) == (None,)
    assert state.vr["w3"].shape == (4, 8) \
        and tuple(specs.vr["w3"]) == ("data", None)
    assert state.vc["w3"].shape == (4, 6) \
        and tuple(specs.vc["w3"]) == ("data", None)
    assert tuple(specs.step) == ()


def test_param_logical_axes_handles_scalars():
    """Rank-0 param leaves (e.g. a scalar temperature) must resolve to
    replicated, not index an empty shape."""
    plan = partition.make_plan(1)
    params = {"w": jnp.zeros((4, 2)), "temp": jnp.zeros(())}
    axes = plan.param_logical_axes(params)
    assert axes["temp"] == ()
    specs = plan.zero_param_specs(params)
    assert tuple(specs["temp"]) == ()
    assert plan.zero_dims(specs)["temp"] == -1


def test_adamw_state_specs_zero_path():
    """On a data>1 mesh AdamW m/v leaves resolve to "data"-sharded on the
    leading dim wherever the data size divides it (1-device mesh: ZeRO
    disabled, everything replicated)."""
    plan = partition.make_plan(1)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((3,))}
    opt = AdamW()
    state = opt.init(params)
    # zero disabled on a 1-shard mesh -> replicated specs
    specs = plan.opt_state_specs(opt, params, state)
    assert all(tuple(s) == () for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)))
    # the resolver itself (what a data=4 mesh uses): divisible leading
    # dims shard, indivisible replicate — verified via the axes tree
    resolved = plan._resolve_axes_tree(
        opt.state_axes(plan.param_logical_axes(params)), state)
    assert tuple(resolved.m["w"]) == ("data", None)  # 8 % 1 == 0
    assert plan.zero_dims(resolved).m["w"] == 0
    assert plan.zero_dims(resolved).step == -1


# ---------------------------------------------------------------------------
# psum-corrected norms + ZeRO update plumbing (1-shard mesh: collectives
# are identities, so the corrected path must equal the plain one)
# ---------------------------------------------------------------------------

def _shard_map_1dev(f, *args):
    mesh = partition.make_mesh(1)
    return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def test_global_norm_psum_correction_matches_plain():
    tree = {"a": jnp.arange(8.0).reshape(4, 2), "b": jnp.ones((3,))}
    dims = {"a": 0, "b": -1}
    plain = global_norm(tree)
    corrected = _shard_map_1dev(
        lambda t: global_norm(t, axis_name=("data",), shard_dims=dims),
        tree)
    np.testing.assert_allclose(np.asarray(corrected), np.asarray(plain),
                               rtol=1e-6)


def test_adamw_zero_update_matches_plain_on_one_shard():
    params = {"w": jnp.ones((4, 2)), "b": jnp.zeros((2,))}
    grads = {"w": jnp.full((4, 2), 3.0), "b": jnp.ones((2,))}
    opt = AdamW(learning_rate=1e-2)
    state = opt.init(params)
    dims = {"w": 0, "b": 0}
    p_ref, s_ref, m_ref = opt.update(grads, state, params)

    def step(p, g, s):
        p2, s2, m = opt.update(g, s, p, axis_name=("data",),
                               shard_dims=dims)
        return p2, s2, m["grad_norm"]

    p_z, s_z, gnorm = _shard_map_1dev(step, params, grads, state)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_z)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref),
                    jax.tree_util.tree_leaves(s_z)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gnorm),
                               np.asarray(m_ref["grad_norm"]), rtol=1e-6)


def test_adafactor_zero_update_matches_plain_on_one_shard():
    params = {"w": jnp.linspace(0.1, 1.0, 12).reshape(4, 3)}
    grads = {"w": jnp.linspace(-1.0, 1.0, 12).reshape(4, 3)}
    opt = Adafactor(learning_rate=1e-2)
    state = opt.init(params)
    p_ref, s_ref, _ = opt.update(grads, state, params)
    p_z, s_z, _ = _shard_map_1dev(
        lambda p, g, s: opt.update(g, s, p, axis_name=("data",),
                                   shard_dims={"w": 0}),
        params, grads, state)
    np.testing.assert_allclose(np.asarray(p_z["w"]),
                               np.asarray(p_ref["w"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_z.vr["w"]),
                               np.asarray(s_ref.vr["w"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_z.vc["w"]),
                               np.asarray(s_ref.vc["w"]), rtol=1e-6)


def test_clip_by_global_norm_keyword_compat():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    np.testing.assert_allclose(float(norm), 20.0, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.abs(clipped["a"]).max()), 0.5,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# kernel dispatch: model shards divide the feature-width budget
# ---------------------------------------------------------------------------

def test_dispatch_partitioned_budgets_model_shards():
    from repro.kernels import dispatch

    was = dispatch.enabled()
    dispatch.enable(True)
    try:
        wide = dispatch.MAX_FEATURE_DIM * 2
        unsharded = dispatch.segment_reduce_decision((1024, wide),
                                                     jnp.float32, 128)
        assert not unsharded.use_kernel
        assert "feature width" in unsharded.reason
        with dispatch.partitioned(model=4):
            sharded = dispatch.segment_reduce_decision((1024, wide),
                                                       jnp.float32, 128)
        assert sharded.use_kernel
        assert "model shards" in sharded.reason
        assert dispatch.model_shards() == 1  # context restored
        # the PR-2 data_parallel alias still works
        with dispatch.data_parallel(8):
            assert dispatch.data_shards() == 8
            assert dispatch.model_shards() == 1
    finally:
        dispatch.enable(was)


# ---------------------------------------------------------------------------
# train_loop: the GSPMD LM step routed through a MeshPlan with ZeRO-1
# ---------------------------------------------------------------------------

def test_make_train_step_with_plan_and_zero1_runs():
    from repro.configs.base import smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.specs import pick_optimizer
    from repro.models.registry import build_model, get_config
    from repro.nn.module import split_params
    from repro.train.train_loop import make_train_step

    cfg = smoke_config(get_config("qwen1.5-4b"))
    model = build_model(cfg)
    opt = pick_optimizer(cfg)
    params, _ = split_params(model.init(jax.random.PRNGKey(0)))
    opt_state = opt.init(params)
    plan = partition.plan_for(make_host_mesh(1, shape=(1, 1)))
    step = make_train_step(model, cfg, opt, plan=plan, zero1=True)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    params, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# 8-device (data=4, model=2) parity + placement + ZeRO memory
# ---------------------------------------------------------------------------

MP_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, "tests")
    import jax, numpy as np
    from jax.sharding import PartitionSpec as P
    from test_graph_sharding import _mag_run, tiny_graph
    from repro.core.graph_tensor import stack_graphs
    from repro.distributed import partition
    from repro.train.optimizer import AdamW

    # --- placement: 2-D specs, node features split over "model" ---------
    plan = partition.make_plan(8, model_parallel=2)
    assert plan.data_size == 4 and plan.model_size == 2, plan.mesh
    stacked = stack_graphs([tiny_graph(i, n_nodes=6, n_edges=8)
                            for i in range(4)])
    specs = plan.graph_specs(stacked)
    leaf_spec = tuple(specs.node_sets["n"].features["h"])
    assert leaf_spec == ("data", None, "model"), leaf_spec
    g, _ = plan.put_super_batch(stacked, np.zeros((4, 2), np.int32))
    leaf = g.node_sets["n"]["h"]          # [4, 6, 4] global
    assert len(leaf.sharding.device_set) == 8, leaf.sharding
    shard = leaf.addressable_shards[0].data.shape
    assert shard == (1, 6, 2), shard      # 1 group x full cap x D/2
    # rank-2 leaves (sizes/adjacency) stay data-only
    adj_spec = tuple(specs.edge_sets["e"].adjacency.source)
    assert adj_spec == ("data", None), adj_spec

    # --- ZeRO-1: optimizer-state bytes shrink by the data factor --------
    params = {"emb": np.zeros((256, 16), np.float32),
              "b": np.zeros((16,), np.float32)}
    opt = AdamW()
    plan1 = partition.make_plan(1)
    s1 = plan1.place_opt_state(opt, params, opt.init(params))
    s4 = plan.place_opt_state(opt, params, opt.init(params))
    b1 = plan1.opt_state_bytes_per_device(s1)
    b4 = plan.opt_state_bytes_per_device(s4)
    shrink = b1 / b4
    assert shrink >= 1.8, (b1, b4)

    # --- loss parity: (data=4, model=2) == 1 device, same 4 groups ------
    one = _mag_run(num_devices=1, num_replicas=4)
    two = _mag_run(num_devices=8, num_replicas=4, model_parallel=2)
    print("MP_PARITY", json.dumps({"one": one.train_loss,
                                   "two": two.train_loss,
                                   "shrink": shrink}))
""")


def test_mp_loss_matches_one_device(tmp_path):
    """8 fake CPU devices folded to (data=4, model=2): feature-sharded
    placement, ZeRO-sharded AdamW state, and the same loss as the
    1-device run on the same 4-group super-batches."""
    script = tmp_path / "mp_parity.py"
    script.write_text(MP_PARITY_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.getcwd())
    assert "MP_PARITY" in res.stdout, (res.stdout[-2000:],
                                       res.stderr[-2000:])
    import json
    payload = json.loads(res.stdout.split("MP_PARITY", 1)[1])
    assert abs(payload["one"] - payload["two"]) < 1e-4, payload
    assert payload["shrink"] >= 1.8, payload
