"""The grad-norm sum of squares (`optimizer._sqsum`) over huge leaves.

A leaf over `SQSUM_BLOCK_ELEMS` elements is reduced in blocks of about that
many elements: an embedding table of a million short rows takes a handful of
loop iterations, a stacked leaf whose single row is over the threshold takes
one row per iteration, and smaller leaves reduce whole as before.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import optimizer
from repro.train.optimizer import _sqsum, clip_by_global_norm, global_norm

# OGBN-MAG's author table: 1,134,649 rows of 128 floats.
AUTHOR_TABLE = jax.ShapeDtypeStruct((1_134_649, 128), jnp.float32)


def _plain(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def _loops(jaxpr):
    """(primitive name, trip count or None) of every loop, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            found.append((eqn.primitive.name, eqn.params.get("length")))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loops(sub)
    return found


def test_author_table_reduces_in_eight_blocks_and_a_tail():
    closed = jax.make_jaxpr(global_norm)([AUTHOR_TABLE])
    assert _loops(closed.jaxpr) == [("scan", 8)]
    # 131,072 rows per block; the 86,073 rows past 8 blocks are reduced
    # outside the loop.
    text = str(closed)
    assert "f32[131072,128] = dynamic_slice" in text
    assert "f32[86073,128] = slice" in text


@pytest.fixture
def small_threshold(monkeypatch):
    monkeypatch.setattr(optimizer, "SQSUM_BLOCK_ELEMS", 64)


@pytest.mark.parametrize("shape,dtype,trips", [
    ((37, 5), jnp.float32, 3),      # 12 rows per block, 1 row of tail
    ((29, 6), jnp.bfloat16, 2),     # 10 rows per block, 9 rows of tail
    ((3, 5, 20), jnp.float32, 3),   # a row of 100 > 64: one row per block
])
def test_blocked_sqsum_matches_plain_sum(small_threshold, shape, dtype,
                                         trips):
    x = jnp.asarray(np.random.default_rng(7).standard_normal(shape), dtype)
    assert _loops(jax.make_jaxpr(_sqsum)(x).jaxpr) == [("scan", trips)]
    got = float(jax.jit(_sqsum)(x))
    want = float(_plain(x))
    assert got == pytest.approx(want, rel=1e-6)


def test_clip_by_global_norm_with_a_blocked_leaf(small_threshold):
    rng = np.random.default_rng(3)
    tree = {"table": jnp.asarray(rng.standard_normal((37, 5)), jnp.float32),
            "bias": jnp.asarray(rng.standard_normal((6,)), jnp.float32)}
    max_norm = 0.5
    clipped, norm = jax.jit(
        lambda t: clip_by_global_norm(t, max_norm))(tree)
    want_norm = jnp.sqrt(_plain(tree["table"]) + _plain(tree["bias"]))
    np.testing.assert_allclose(norm, want_norm, rtol=1e-6)
    scale = jnp.minimum(1.0, max_norm / (want_norm + 1e-9))
    assert float(scale) < 1.0
    for name, g in tree.items():
        np.testing.assert_allclose(clipped[name], g * scale, rtol=1e-6)


@pytest.mark.parametrize("shape,dtype", [
    ((59_965, 128), jnp.float32),        # field table
    ((4096, 4096), jnp.float32),         # exactly the threshold
    ((1024, 1024), jnp.bfloat16),
    ((20 * 1024 * 1024,), jnp.float32),  # over it, but 1-D
])
def test_leaf_under_threshold_lowers_as_before(shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype)
    assert str(jax.make_jaxpr(_sqsum)(x)) == str(jax.make_jaxpr(_plain)(x))
    assert not _loops(jax.make_jaxpr(_sqsum)(x).jaxpr)
