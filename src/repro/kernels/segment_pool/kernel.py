"""Pallas TPU kernel: segment reduction (sum/max/min) over edge values.

The GNN pooling primitive (paper §4.1 pool_edges_to_node), rethought for
TPU: GPU implementations scatter with atomics (warp-per-row CSR); the TPU
has no atomics but its grid iterates *sequentially* per core, so we keep
the [N, D] output accumulator resident in VMEM across edge-block grid steps
and turn the scatter itself into an MXU matmul:

    out += onehot(seg_ids_block) @ values_block       (sum)
    out  = max(out, masked-broadcast max)             (max; min = -max(-x))

One HBM pass over edge values; the one-hot [E_blk, N] never leaves VMEM.
All reductions accumulate in fp32 regardless of input dtype (bf16 inputs
would otherwise lose low bits on every scatter-add) and cast on exit.

`segment_pool_runs` is the CSR-run variant for edge streams sorted by
target (BatchPlan.edges_sorted_by_target): a segmented Hillis-Steele scan
folds each contiguous run of equal ids, then one predicated [1, D]
read-modify-write per *run end* lands it in the accumulator.  No [E_blk, N]
one-hot and no [E_blk, N, D] masked broadcast, so the per-edge VMEM cost
is O(D) instead of O(N) / O(N*D) and max/min stop forcing tiny blocks.
The variant is correct for ANY id layout (a "run" is just a maximal
stretch of equal consecutive ids); sortedness only collapses each segment
into a single run, so dispatch treats the layout bit purely as a
performance hint, never a correctness requirement.

Constraints: the fp32 accumulator (N * D * 4B) plus one edge block
(E_blk * N one-hot + E_blk * D values for the one-hot variant; E_blk * D
scan state for the runs variant) must fit the VMEM budget.  Callers
should route through repro.kernels.dispatch, which sizes E_blk from that
budget (see dispatch.choose_e_block) and falls back to the jnp reference
for out-of-envelope shapes; `e_block=None` here applies the same heuristic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _seg_sum_kernel(values_ref, segs_ref, out_ref, *, n_segments: int,
                    e_block: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = values_ref[...]  # [E_blk, D]
    segs = segs_ref[...]    # [E_blk, 1] int32 (padding rows -> n_segments)
    onehot = (segs == jax.lax.broadcasted_iota(
        jnp.int32, (e_block, n_segments), 1)).astype(vals.dtype)
    # accumulate in fp32 (out buffer is fp32; cast back in the wrapper)
    out_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [N, D]


def _seg_max_kernel(values_ref, segs_ref, out_ref, *, n_segments: int,
                    e_block: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, NEG_INF)

    vals = values_ref[...].astype(jnp.float32)
    segs = segs_ref[...]
    # [E_blk, N, D] masked broadcast, reduced over the edge dim.  The mask
    # is compared at rank 3 directly: Mosaic cannot reshape an i1 vector.
    ids = jax.lax.broadcasted_iota(
        jnp.int32, (e_block, n_segments, vals.shape[1]), 1)
    contrib = jnp.where(segs[:, :, None] == ids, vals[:, None, :], NEG_INF)
    out_ref[...] = jnp.maximum(out_ref[...], contrib.max(axis=0))


def segmented_run_scan(x: jnp.ndarray, segs: jnp.ndarray, e_block: int,
                       combine, identity) -> jnp.ndarray:
    """Segmented inclusive scan (Hillis-Steele): after log2(E_blk) rounds
    x[i] combines every row of i's run up to and including i.  `flag`
    marks run heads and is OR-propagated so a combine never reaches
    across a run boundary, which keeps unsorted ids correct (two runs
    of the same segment fold independently and meet in the accumulator).
    x: [E_blk, D]; segs: [E_blk, 1] int32.  Shared with edge_mpnn_runs.

    Shifts are sublane rotations (`pltpu.roll`) with the wrapped rows
    masked off, and flags are int32 0/1: Mosaic can neither cast i1
    vectors to i32 nor concatenate at unaligned sublane offsets."""
    row = jax.lax.broadcasted_iota(jnp.int32, segs.shape, 0)
    x_row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    prev = jnp.where(row == 0, -1, pltpu.roll(segs, 1, 0))
    flag = jnp.where(segs != prev, 1, 0)
    dist = 1
    while dist < e_block:
        x_sh = jnp.where(x_row < dist, identity, pltpu.roll(x, dist, 0))
        f_sh = jnp.where(row < dist, 1, pltpu.roll(flag, dist, 0))
        x = jnp.where(jnp.broadcast_to(flag, x.shape) > 0, x,
                      combine(x_sh, x))
        flag = jnp.maximum(flag, f_sh)
        dist *= 2
    return x


def _seg_runs_kernel(values_ref, segs_ref, out_ref, x_scr, *,
                     n_segments: int, e_block: int, reduce: str):
    step = pl.program_id(0)
    if reduce == "sum":
        identity, combine = 0.0, jnp.add
    else:
        identity, combine = NEG_INF, jnp.maximum

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, identity)

    vals = values_ref[...].astype(jnp.float32)  # [E_blk, D]
    segs = segs_ref[...]                        # [E_blk, 1] int32
    x_scr[...] = segmented_run_scan(vals, segs, e_block, combine, identity)

    # One predicated [1, D] read-modify-write per run END.  A run split
    # across blocks scatters once per block with the same combine, which
    # is associative, so block boundaries need no special casing.
    def _scatter(i, carry):
        seg_i = segs_ref[i, 0]
        nxt = jnp.where(i + 1 < e_block,
                        segs_ref[jnp.minimum(i + 1, e_block - 1), 0], -1)

        @pl.when((seg_i != nxt) & (seg_i < n_segments))
        def _():
            row = x_scr[pl.ds(i, 1), :]
            cur = out_ref[pl.ds(seg_i, 1), :]
            out_ref[pl.ds(seg_i, 1), :] = combine(cur, row)

        return carry

    jax.lax.fori_loop(0, e_block, _scatter, 0)


@functools.partial(jax.jit, static_argnames=("n_segments", "e_block",
                                             "reduce", "interpret"))
def segment_pool_runs(values: jnp.ndarray, seg_ids: jnp.ndarray, *,
                      n_segments: int, reduce: str = "sum",
                      e_block: int | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """CSR-run segment_pool: same contract as `segment_pool` (seg_ids >=
    n_segments mark padding, empty segments yield 0, fp32 accumulation),
    but scans contiguous runs instead of materializing one-hots.  Fastest
    when ids arrive sorted (one run per segment); still correct unsorted."""
    if reduce == "min":
        return -segment_pool_runs(-values, seg_ids, n_segments=n_segments,
                                  reduce="max", e_block=e_block,
                                  interpret=interpret)
    e, d = values.shape
    if e_block is None:
        from repro.kernels import dispatch as _dispatch
        e_block = _dispatch.choose_e_block(n_segments, d,
                                           values.dtype.itemsize,
                                           reduce=reduce, n_edges=e,
                                           variant="runs")
        if e_block == 0:
            raise ValueError(
                f"segment_pool_runs: [{n_segments}, {d}] accumulator "
                "exceeds the VMEM budget; use repro.kernels.dispatch for "
                "the fallback")
    pad = (-e) % e_block
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, pad),
                          constant_values=n_segments)
    e_tot = values.shape[0]
    seg2d = seg_ids.astype(jnp.int32).reshape(-1, 1)
    out = pl.pallas_call(
        functools.partial(_seg_runs_kernel, n_segments=n_segments,
                          e_block=e_block, reduce=reduce),
        grid=(e_tot // e_block,),
        in_specs=[
            pl.BlockSpec((e_block, d), lambda i: (i, 0)),
            pl.BlockSpec((e_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_segments, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_segments, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((e_block, d), jnp.float32)],
        interpret=interpret,
    )(values, seg2d)
    if reduce == "max":
        out = jnp.where(out <= NEG_INF / 2, 0, out)
    return out.astype(values.dtype)


@functools.partial(jax.jit, static_argnames=("n_segments", "e_block",
                                             "reduce", "interpret"))
def segment_pool(values: jnp.ndarray, seg_ids: jnp.ndarray, *,
                 n_segments: int, reduce: str = "sum",
                 e_block: int | None = None,
                 interpret: bool = False) -> jnp.ndarray:
    """values: [E, D]; seg_ids: [E] int32 in [0, n_segments) or >= n_segments
    for padding rows.  Returns [n_segments, D]; empty segments yield 0
    (sum identity) for every reduction.  e_block=None sizes the edge block
    from the VMEM budget."""
    if reduce == "min":
        return -segment_pool(-values, seg_ids, n_segments=n_segments,
                             reduce="max", e_block=e_block,
                             interpret=interpret)
    e, d = values.shape
    if e_block is None:
        from repro.kernels import dispatch as _dispatch
        e_block = _dispatch.choose_e_block(n_segments, d,
                                           values.dtype.itemsize,
                                           reduce=reduce, n_edges=e)
        if e_block == 0:  # out of envelope; dispatch should have caught it
            raise ValueError(
                f"segment_pool: [{n_segments}, {d}] accumulator exceeds the "
                "VMEM budget; use repro.kernels.dispatch for the fallback")
    pad = (-e) % e_block
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, pad),
                          constant_values=n_segments)
    e_tot = values.shape[0]
    seg2d = seg_ids.astype(jnp.int32).reshape(-1, 1)
    kernel = _seg_sum_kernel if reduce == "sum" else _seg_max_kernel
    out = pl.pallas_call(
        functools.partial(kernel, n_segments=n_segments, e_block=e_block),
        grid=(e_tot // e_block,),
        in_specs=[
            pl.BlockSpec((e_block, d), lambda i: (i, 0)),
            pl.BlockSpec((e_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_segments, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_segments, d), jnp.float32),
        interpret=interpret,
    )(values, seg2d)
    if reduce == "max":
        out = jnp.where(out <= NEG_INF / 2, 0, out)
    return out.astype(values.dtype)
