"""Pallas TPU kernel: fused MPNN edge convolution (the paper's Eq. 2/Fig. 7
hot path) — gather src/tgt states, per-edge MLP message, segment-sum pool,
all in one VMEM pass.

    msg_e = act( [h_src(e) ; h_tgt(e)] @ W + b )
    out_v = sum_{e: tgt(e)=v} msg_e

TPU adaptation of FusedMM/GE-SpMM (GPU warp-CSR + atomics have no TPU
analogue): node states are VMEM-resident, per-edge gathers are rolled into
a one-hot MXU matmul (gather = onehot(src) @ H), the message transform is a
dense MXU matmul over the edge block, and the scatter-add is the transposed
one-hot matmul accumulated across sequential grid steps.  One HBM read of
the edge list; node/message traffic stays on-chip.

The message transform and the scatter-add both run with fp32 accumulation
(bf16 inputs would otherwise lose low bits on every per-edge add); the
fp32 accumulator is cast back to the input dtype on exit.

`edge_mpnn_runs` is the CSR-run variant for edge streams sorted by target:
gathers become per-row dynamic loads into a VMEM scratch (no [E_blk, n_src]
one-hot), the message matmul is unchanged, and the scatter-add becomes a
segmented run scan plus one predicated row update per run end (no
[E_blk, n_tgt] transposed one-hot).  Per-edge VMEM drops from O(n_src +
n_tgt) to O(Ds + Dt + M), so far larger edge blocks fit.  Like
segment_pool_runs it is correct for any edge order; sorted targets just
collapse each node's in-edges into a single run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTIVATIONS = ("relu", "gelu", "identity")


def _edge_mpnn_kernel(h_src_ref, h_tgt_ref, src_ref, tgt_ref, w_ref, b_ref,
                      out_ref, *, e_block: int, n_src: int, n_tgt: int,
                      activation: str):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    src = src_ref[...]  # [E_blk, 1]
    tgt = tgt_ref[...]  # [E_blk, 1] (padding -> n_tgt, i.e. out of range)
    # gather via one-hot matmuls (MXU-shaped, no dynamic indexing)
    oh_src = (src == jax.lax.broadcasted_iota(
        jnp.int32, (e_block, n_src), 1)).astype(h_src_ref.dtype)
    oh_tgt = (tgt == jax.lax.broadcasted_iota(
        jnp.int32, (e_block, n_tgt), 1)).astype(h_tgt_ref.dtype)
    # fp32 accumulation (Mosaic requires it); a one-hot row selects one
    # value exactly, so the cast back to the state dtype is lossless
    hs = jax.lax.dot_general(
        oh_src, h_src_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(h_src_ref.dtype)
    ht = jax.lax.dot_general(
        oh_tgt, h_tgt_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(h_tgt_ref.dtype)
    x = jnp.concatenate([hs, ht], axis=-1)
    # message transform in fp32: bf16 inputs round once here, not per-op
    msg = jax.lax.dot_general(x, w_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    msg = msg + b_ref[...].astype(jnp.float32)
    if activation == "relu":
        msg = jnp.maximum(msg, 0)
    elif activation == "gelu":
        msg = jax.nn.gelu(msg)
    # scatter-add via transposed one-hot (padding tgt rows are all-zero),
    # accumulated in the fp32 out buffer
    out_ref[...] += jax.lax.dot_general(
        oh_tgt.astype(jnp.float32), msg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _edge_mpnn_runs_kernel(h_src_ref, h_tgt_ref, src_ref, tgt_ref, w_ref,
                           b_ref, out_ref, hs_scr, ht_scr, m_scr, *,
                           e_block: int, n_src: int, n_tgt: int,
                           activation: str, x_dtype):
    from repro.kernels.segment_pool.kernel import segmented_run_scan
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # gather via per-row dynamic loads into scratch — O(Ds+Dt) per edge
    # instead of the O(n_src + n_tgt) one-hots.  States arrive as fp32
    # (see edge_mpnn_runs) and each side has its own full-width scratch:
    # Mosaic refuses single-row dynamic loads and stores on packed 16-bit
    # rows, and a dynamic row store into a lane slice of a wider buffer.
    def _gather(i, carry):
        s = jnp.minimum(src_ref[i, 0], n_src - 1)
        t = jnp.minimum(tgt_ref[i, 0], n_tgt - 1)  # clamp padding rows
        hs_scr[pl.ds(i, 1), :] = h_src_ref[pl.ds(s, 1), :]
        ht_scr[pl.ds(i, 1), :] = h_tgt_ref[pl.ds(t, 1), :]
        return carry

    jax.lax.fori_loop(0, e_block, _gather, 0)
    # back to the caller's state dtype (exact: the values came from it)
    x = jnp.concatenate([hs_scr[...], ht_scr[...]], axis=-1).astype(x_dtype)
    # message transform in fp32: bf16 inputs round once here, not per-op
    msg = jax.lax.dot_general(x, w_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    msg = msg + b_ref[...].astype(jnp.float32)
    if activation == "relu":
        msg = jnp.maximum(msg, 0)
    elif activation == "gelu":
        msg = jax.nn.gelu(msg)
    # scatter-add as a segmented run scan over tgt plus one predicated row
    # update per run end (padding rows carry tgt = n_tgt: they form their
    # own runs and the update predicate skips them)
    tgt = tgt_ref[...]  # [E_blk, 1]
    m_scr[...] = segmented_run_scan(msg, tgt, e_block, jnp.add, 0.0)

    def _scatter(i, carry):
        t_i = tgt_ref[i, 0]
        nxt = jnp.where(i + 1 < e_block,
                        tgt_ref[jnp.minimum(i + 1, e_block - 1), 0], -1)

        @pl.when((t_i != nxt) & (t_i < n_tgt))
        def _():
            row = m_scr[pl.ds(i, 1), :]
            out_ref[pl.ds(t_i, 1), :] = out_ref[pl.ds(t_i, 1), :] + row

        return carry

    jax.lax.fori_loop(0, e_block, _scatter, 0)


@functools.partial(jax.jit, static_argnames=("n_src", "n_tgt", "e_block",
                                             "activation", "interpret"))
def edge_mpnn_runs(h_src: jnp.ndarray, h_tgt: jnp.ndarray, src: jnp.ndarray,
                   tgt: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                   n_src: int, n_tgt: int, e_block: int | None = None,
                   activation: str = "relu", interpret: bool = False
                   ) -> jnp.ndarray:
    """CSR-run edge_mpnn: same contract as `edge_mpnn` (padding edges carry
    tgt >= n_tgt, fp32 accumulation, returns [n_tgt, M]), but gathers with
    dynamic row loads and pools with a run scan.  Fastest when tgt arrives
    sorted (one run per receiver); still correct for any edge order."""
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unsupported activation {activation!r}; "
                         f"expected one of {_ACTIVATIONS}")
    e = src.shape[0]
    m = w.shape[1]
    ds, dt = h_src.shape[1], h_tgt.shape[1]
    x_dtype = h_src.dtype
    if e_block is None:
        from repro.kernels import dispatch as _dispatch
        e_block = _dispatch.choose_mpnn_e_block(
            n_src, n_tgt, ds, dt, m, h_src.dtype.itemsize, n_edges=e,
            variant="runs")
        if e_block == 0:
            raise ValueError(
                "edge_mpnn_runs: working set exceeds the VMEM budget; use "
                "repro.kernels.dispatch for the fallback")
    pad = (-e) % e_block
    if pad:
        src = jnp.pad(src, (0, pad))
        tgt = jnp.pad(tgt, (0, pad), constant_values=n_tgt)
    e_tot = src.shape[0]
    out = pl.pallas_call(
        functools.partial(_edge_mpnn_runs_kernel, e_block=e_block,
                          n_src=n_src, n_tgt=n_tgt, activation=activation,
                          x_dtype=x_dtype),
        grid=(e_tot // e_block,),
        in_specs=[
            pl.BlockSpec((n_src, ds), lambda i: (0, 0)),
            pl.BlockSpec((n_tgt, dt), lambda i: (0, 0)),
            pl.BlockSpec((e_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((e_block, 1), lambda i: (i, 0)),
            pl.BlockSpec(w.shape, lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_tgt, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tgt, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((e_block, ds), jnp.float32),
                        pltpu.VMEM((e_block, dt), jnp.float32),
                        pltpu.VMEM((e_block, m), jnp.float32)],
        interpret=interpret,
    )(h_src.astype(jnp.float32), h_tgt.astype(jnp.float32),
      src.astype(jnp.int32).reshape(-1, 1),
      tgt.astype(jnp.int32).reshape(-1, 1), w, b.reshape(1, -1))
    return out.astype(x_dtype)


@functools.partial(jax.jit, static_argnames=("n_src", "n_tgt", "e_block",
                                             "activation", "interpret"))
def edge_mpnn(h_src: jnp.ndarray, h_tgt: jnp.ndarray, src: jnp.ndarray,
              tgt: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
              n_src: int, n_tgt: int, e_block: int | None = None,
              activation: str = "relu", interpret: bool = False
              ) -> jnp.ndarray:
    """h_src: [n_src, Ds]; h_tgt: [n_tgt, Dt]; src/tgt: [E] int32 (padding
    edges must carry tgt >= n_tgt); w: [Ds+Dt, M]; b: [M].
    Returns pooled messages [n_tgt, M].  e_block=None sizes the edge block
    from the VMEM budget."""
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unsupported activation {activation!r}; "
                         f"expected one of {_ACTIVATIONS}")
    e = src.shape[0]
    m = w.shape[1]
    if e_block is None:
        from repro.kernels import dispatch as _dispatch
        e_block = _dispatch.choose_mpnn_e_block(
            n_src, n_tgt, h_src.shape[1], h_tgt.shape[1], m,
            h_src.dtype.itemsize, n_edges=e)
        if e_block == 0:
            raise ValueError(
                "edge_mpnn: working set exceeds the VMEM budget; use "
                "repro.kernels.dispatch for the fallback")
    pad = (-e) % e_block
    if pad:
        src = jnp.pad(src, (0, pad))
        tgt = jnp.pad(tgt, (0, pad), constant_values=n_tgt)
    e_tot = src.shape[0]
    out = pl.pallas_call(
        functools.partial(_edge_mpnn_kernel, e_block=e_block, n_src=n_src,
                          n_tgt=n_tgt, activation=activation),
        grid=(e_tot // e_block,),
        in_specs=[
            pl.BlockSpec((n_src, h_src.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((n_tgt, h_tgt.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((e_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((e_block, 1), lambda i: (i, 0)),
            pl.BlockSpec(w.shape, lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_tgt, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tgt, m), jnp.float32),
        interpret=interpret,
    )(h_src, h_tgt, src.astype(jnp.int32).reshape(-1, 1),
      tgt.astype(jnp.int32).reshape(-1, 1), w, b.reshape(1, -1))
    return out.astype(h_src.dtype)
