"""Roofline methodology validation (DESIGN.md §7).

Confirms on this jax install that `compiled.cost_analysis()` counts scan
bodies once (the reason roofline FLOPs are analytic), and validates the
analytic FLOP model against cost_analysis at single-layer granularity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, smoke_config
from repro.launch.roofline import step_flops
from repro.models.registry import build_model, get_config
from repro.nn.module import split_params


def test_cost_analysis_counts_scan_body_once():
    def scanned(x, ws):
        def body(h, w):
            return h @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w8 = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    w1 = jax.ShapeDtypeStruct((1, 128, 128), jnp.float32)
    f8 = jax.jit(scanned).lower(x, w8).compile().cost_analysis()["flops"]
    f1 = jax.jit(scanned).lower(x, w1).compile().cost_analysis()["flops"]
    assert f8 == pytest.approx(f1, rel=0.01), \
        "cost_analysis no longer undercounts scans — roofline can switch " \
        "to HLO FLOPs directly"


def test_analytic_flops_match_cost_analysis_per_layer():
    """Analytic per-layer FLOPs ≈ HLO FLOPs of a 1-layer forward."""
    cfg = dataclasses.replace(smoke_config(get_config("deepseek-7b")),
                              num_layers=1, remat="none")
    model = build_model(cfg)
    params, _ = split_params(model.init(jax.random.PRNGKey(0)))
    b, s = 2, 64
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    pspec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    cost = (jax.jit(lambda p, t: model(p, t).logits)
            .lower(pspec, toks).compile().cost_analysis())
    hlo_flops = cost["flops"]
    shape = ShapeConfig("t", s, b, "prefill")  # fwd-only
    analytic = step_flops(cfg, shape)["compiled_flops"]
    # within 2x: analytic covers matmuls; HLO adds softmax/norm vector ops
    assert 0.5 < analytic / hlo_flops < 2.0, (analytic, hlo_flops)


def test_moe_active_vs_total_params():
    cfg = get_config("arctic-480b")
    total = cfg.param_count_estimate()
    active = cfg.active_param_count_estimate()
    assert total > 4.0e11, total       # ~480B
    assert active < 0.05 * total       # top-2 of 128 experts + dense
