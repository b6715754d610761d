"""Optimizers (AdamW, Adafactor, SGD-momentum) + schedules + clipping.

No optax in this environment; implemented directly on param pytrees.
Moments may be stored in a reduced dtype (bf16) for the >=100B archs — an
explicit distributed-memory trick recorded in EXPERIMENTS.md.
Optimizer state reuses the params' logical sharding axes, so FSDP (ZeRO-3)
sharding of m/v falls out of the same rule table.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

def warmup_cosine(peak_lr: float, warmup_steps: int, total_steps: int,
                  final_frac: float = 0.1) -> Callable[[jnp.ndarray], jnp.ndarray]:
    def schedule(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(warmup_steps, 1)
        prog = jnp.clip((step - warmup_steps)
                        / max(total_steps - warmup_steps, 1), 0.0, 1.0)
        cos = peak_lr * (final_frac + (1 - final_frac)
                         * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup_steps, warm, cos)

    return schedule


def constant_lr(lr: float):
    return lambda step: jnp.asarray(lr, jnp.float32)


# ---------------------------------------------------------------------------
# Gradient clipping
# ---------------------------------------------------------------------------

def _f32_sqsum(x) -> jnp.ndarray:
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def _sqsum(x) -> jnp.ndarray:
    """Sum of squares in fp32 without materialising an fp32 copy of huge
    leaves (the CPU pipeline does not fuse convert+square into the reduce
    for multi-GiB tensors).

    A leaf over ``SQSUM_BLOCK_ELEMS`` elements with ``ndim >= 2`` is reduced
    in blocks of whole leading-dim rows, each block about
    ``SQSUM_BLOCK_ELEMS`` elements, read in place by a ``fori_loop`` with the
    remainder rows reduced after it.  Blocks follow the element count, not
    the row count, because the leaves that cross the threshold come in both
    shapes: a stacked-layer weight has a few dozen rows of millions of
    elements (one row per block, the fp32 temp bounded by one row), while an
    embedding table has a million rows of 128 (one row per iteration would
    be a million-iteration loop of 512-byte reductions).  Smaller leaves
    reduce whole."""
    if x.size <= SQSUM_BLOCK_ELEMS or x.ndim < 2:
        return _f32_sqsum(x)
    rows = max(1, SQSUM_BLOCK_ELEMS // math.prod(x.shape[1:]))
    n_full = x.shape[0] // rows
    total = jax.lax.fori_loop(
        0, n_full,
        lambda i, acc: acc + _f32_sqsum(
            jax.lax.dynamic_slice_in_dim(x, i * rows, rows)),
        jnp.zeros((), jnp.float32))
    if n_full * rows < x.shape[0]:
        total = total + _f32_sqsum(x[n_full * rows:])
    return total


def global_norm(tree: PyTree, *, axis_name=None,
                shard_dims: PyTree | None = None) -> jnp.ndarray:
    """L2 norm over a gradient tree.

    Under ZeRO-1 (repro.distributed.partition) each leaf may be this data
    shard's *slice*: pass ``axis_name`` (the data mesh axes) and
    ``shard_dims`` (per-leaf int, -1 = replicated) and the squared sum of
    sliced leaves is psum-corrected across shards, while replicated
    leaves contribute once — so every shard computes the exact full norm.
    """
    if axis_name is None or shard_dims is None:
        leaves = jax.tree_util.tree_leaves(tree)
        return jnp.sqrt(sum(_sqsum(x) for x in leaves))
    leaves = jax.tree_util.tree_leaves(tree)
    dims = jax.tree_util.tree_leaves(shard_dims)
    assert len(leaves) == len(dims), (len(leaves), len(dims))
    local = sum((_sqsum(x) for x, d in zip(leaves, dims) if d >= 0),
                jnp.zeros((), jnp.float32))
    repl = sum((_sqsum(x) for x, d in zip(leaves, dims) if d < 0),
               jnp.zeros((), jnp.float32))
    return jnp.sqrt(jax.lax.psum(local, axis_name) + repl)


def clip_by_global_norm(tree: PyTree, max_norm: float, *, axis_name=None,
                        shard_dims: PyTree | None = None):
    norm = global_norm(tree, axis_name=axis_name, shard_dims=shard_dims)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    # multiply in each leaf's own dtype: `g * f32_scalar` would otherwise
    # materialise an fp32 copy of the whole gradient tree.
    return jax.tree_util.tree_map(
        lambda g: g * scale.astype(g.dtype), tree), norm


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

# Leaves larger than this (elements, pre-sharding) have their elementwise
# update applied via lax.map over the leading (stacked-layer) dim: the fp32
# working copies then cover one layer slice at a time instead of the whole
# stacked tensor.  Crucial for the >=100B archs (arctic's stacked expert
# weight is 156B params; an fp32 temp of its per-device shard is 2.4 GiB —
# times several temps times three such leaves without chunking).
CHUNKED_UPDATE_THRESHOLD = 64 * 1024 * 1024

# Leaves larger than this (elements) have their grad-norm sum of squares
# reduced in blocks of about this many elements (`_sqsum`).
SQSUM_BLOCK_ELEMS = 16 * 1024 * 1024


def _maybe_chunked(fn, *leaves):
    """Apply an elementwise-per-slice update leaf-wise, chunking the leading
    dim when the leaf is huge.  fn(*slices) -> tuple of slices."""
    lead = leaves[0]
    if lead.size <= CHUNKED_UPDATE_THRESHOLD or lead.ndim < 3:
        return fn(*leaves)
    return jax.lax.map(lambda xs: fn(*xs), leaves)


class AdamWState(NamedTuple):
    step: jnp.ndarray
    m: PyTree
    v: PyTree


@dataclasses.dataclass(frozen=True)
class AdamW:
    learning_rate: Callable | float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    moment_dtype: Any = jnp.float32
    max_grad_norm: float = 1.0

    def _lr(self, step):
        if callable(self.learning_rate):
            return self.learning_rate(step)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def init(self, params: PyTree) -> AdamWState:
        zeros = lambda p: jnp.zeros(p.shape, self.moment_dtype)
        return AdamWState(jnp.zeros((), jnp.int32),
                          jax.tree_util.tree_map(zeros, params),
                          jax.tree_util.tree_map(zeros, params))

    @jax.named_scope("optimizer")
    def update(self, grads: PyTree, state: AdamWState, params: PyTree, *,
               axis_name=None, shard_dims: PyTree | None = None
               ) -> tuple[PyTree, AdamWState, dict]:
        """ZeRO-1: with ``axis_name``/``shard_dims`` the inputs are this
        data shard's slices; AdamW's update is elementwise, so only the
        clipping norm needs the cross-shard psum correction.

        Runs under `jax.named_scope` ``optimizer``, with ``clip`` around
        the global-norm clip and ``update`` around the elementwise step."""
        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, self.max_grad_norm,
                                               axis_name=axis_name,
                                               shard_dims=shard_dims)
        step = state.step + 1
        b1, b2 = self.b1, self.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        lr = self._lr(step)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            mh = m32 / bc1
            vh = v32 / bc2
            delta = mh / (jnp.sqrt(vh) + self.eps)
            delta = delta + self.weight_decay * p.astype(jnp.float32)
            new_p = p.astype(jnp.float32) - lr * delta
            return (new_p.astype(p.dtype), m32.astype(self.moment_dtype),
                    v32.astype(self.moment_dtype))

        with jax.named_scope("update"):
            out = jax.tree_util.tree_map(
                lambda *ls: _maybe_chunked(upd, *ls),
                params, grads, state.m, state.v)
        new_params = jax.tree_util.tree_map(lambda o: o[0], out,
                                            is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree_util.tree_map(lambda o: o[1], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree_util.tree_map(lambda o: o[2], out,
                                       is_leaf=lambda x: isinstance(x, tuple))
        return (new_params, AdamWState(step, new_m, new_v),
                {"grad_norm": gnorm, "learning_rate": lr})

    def state_axes(self, param_axes: PyTree) -> "AdamWState":
        """Optimizer-state logical axes mirror the params'."""
        return AdamWState((), param_axes, param_axes)


# ---------------------------------------------------------------------------
# Adafactor (factored second moment; for the >=100B archs)
# ---------------------------------------------------------------------------

class AdafactorState(NamedTuple):
    step: jnp.ndarray
    vr: PyTree  # row second-moment (or full v for <2D leaves)
    vc: PyTree  # col second-moment (or unused zeros)


@dataclasses.dataclass(frozen=True)
class Adafactor:
    learning_rate: Callable | float = 1e-3
    decay: float = 0.8  # beta2 exponent: 1 - step^-decay
    eps: float = 1e-30
    clip_threshold: float = 1.0
    weight_decay: float = 0.0
    # T5X-style: no global grad-norm clip — Adafactor's rms_u update clip
    # substitutes, and skipping it avoids full-gradient-tree fp32 temps.
    max_grad_norm: float | None = None

    def _lr(self, step):
        if callable(self.learning_rate):
            return self.learning_rate(step)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def _factored(self, p) -> bool:
        return p.ndim >= 2

    def init(self, params: PyTree) -> AdafactorState:
        def vr(p):
            if self._factored(p):
                return jnp.zeros(p.shape[:-1], jnp.float32)
            return jnp.zeros(p.shape, jnp.float32)

        def vc(p):
            if self._factored(p):
                return jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
            return jnp.zeros((), jnp.float32)

        return AdafactorState(jnp.zeros((), jnp.int32),
                              jax.tree_util.tree_map(vr, params),
                              jax.tree_util.tree_map(vc, params))

    @jax.named_scope("optimizer")
    def update(self, grads, state, params, *, axis_name=None,
               shard_dims: PyTree | None = None):
        """ZeRO-1: with ``axis_name``/``shard_dims`` the inputs are this
        data shard's slices.  Unlike AdamW the factored statistics are
        not elementwise — any mean that reduces over a sliced dim (the
        column stats and rms normalizers of a row-sliced 2-D leaf) is
        pmean-corrected so every shard reproduces the replicated math.

        Scopes as AdamW's: ``optimizer``, ``clip``, ``update``."""
        if self.max_grad_norm is not None:
            with jax.named_scope("clip"):
                grads, gnorm = clip_by_global_norm(
                    grads, self.max_grad_norm, axis_name=axis_name,
                    shard_dims=shard_dims)
        else:
            gnorm = jnp.zeros((), jnp.float32)
        step = state.step + 1
        beta2 = 1.0 - step.astype(jnp.float32) ** (-self.decay)
        lr = self._lr(step)

        def upd(p, g, vr, vc, shard_dim=-1):
            # shard_dim >= 0: leaf is a ZeRO slice along that dim (slices
            # are equal-sized, so pmean-of-means is the global mean)
            def corr(x, over_dim):
                if axis_name is not None and shard_dim == over_dim:
                    return jax.lax.pmean(x, axis_name)
                return x
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + self.eps
            if self._factored(p):
                vr_n = beta2 * vr + (1 - beta2) * corr(
                    g2.mean(axis=-1), p.ndim - 1)
                vc_n = beta2 * vc + (1 - beta2) * corr(
                    g2.mean(axis=-2), p.ndim - 2)
                rbar = corr(vr_n.mean(axis=-1, keepdims=True), p.ndim - 2)
                denom = (vr_n / jnp.maximum(rbar, self.eps))[..., None] \
                    * vc_n[..., None, :]
                u = g32 * jax.lax.rsqrt(denom + self.eps)
            else:
                vr_n = beta2 * vr + (1 - beta2) * g2
                vc_n = vc
                u = g32 * jax.lax.rsqrt(vr_n + self.eps)
            msq = jnp.mean(jnp.square(u))
            if axis_name is not None and shard_dim >= 0:
                msq = jax.lax.pmean(msq, axis_name)
            rms_u = jnp.sqrt(msq + 1e-12)
            u = u / jnp.maximum(1.0, rms_u / self.clip_threshold)
            new_p = (p.astype(jnp.float32) - lr *
                     (u + self.weight_decay * p.astype(jnp.float32)))
            return new_p.astype(p.dtype), vr_n, vc_n

        # chunked update keeps fp32 working copies to one layer slice;
        # NB the rms_u clip then applies per leading-dim slice (documented).
        # ZeRO slices skip chunking (they are 1/n_shards-sized already).
        dims = (shard_dims if shard_dims is not None
                else jax.tree_util.tree_map(lambda p: -1, params))
        with jax.named_scope("update"):
            out = jax.tree_util.tree_map(
                lambda p, g, vr, vc, d: (
                    upd(p, g, vr, vc, d) if d >= 0
                    else _maybe_chunked(upd, p, g, vr, vc)),
                params, grads, state.vr, state.vc, dims)
        pick = lambda i: jax.tree_util.tree_map(
            lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return (pick(0), AdafactorState(step, pick(1), pick(2)),
                {"grad_norm": gnorm, "learning_rate": lr})

    def state_axes(self, param_axes: PyTree) -> "AdafactorState":
        def vr_ax(ax):
            return tuple(ax[:-1]) if len(ax) >= 2 else tuple(ax)

        def vc_ax(ax):
            return tuple(ax[:-2]) + tuple(ax[-1:]) if len(ax) >= 2 else ()

        t = lambda f: jax.tree_util.tree_map(
            f, param_axes, is_leaf=lambda x: isinstance(x, tuple))
        return AdafactorState((), t(vr_ax), t(vc_ax))


def make_optimizer(kind: str, lr, *, total_steps: int = 10000,
                   warmup: int = 200, moment_dtype=jnp.float32,
                   weight_decay: float = 0.1):
    sched = warmup_cosine(lr, warmup, total_steps)
    if kind == "adamw":
        return AdamW(learning_rate=sched, moment_dtype=moment_dtype,
                     weight_decay=weight_decay)
    if kind == "adafactor":
        return Adafactor(learning_rate=sched, weight_decay=weight_decay)
    raise ValueError(kind)
