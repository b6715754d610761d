"""Unified dispatch for segment-shaped ops: one registry + eligibility layer.

Every segment-shaped reduction in `repro.core.ops` (edge->node pooling,
segment softmax, context pooling, node degree) and the fused edge
convolution in `repro.core.convolutions` route through this module, which
decides per call site whether the Pallas kernel or the jnp reference runs.
This replaces the per-op inline `_KERNELS_ENABLED and ndim == 2` guards:
eligibility lives in exactly one place and is explainable (every decision
carries a reason string, surfaced by `GraphUpdate.describe_dispatch`).

Decision inputs (static at trace time, so dispatch is jit-safe):

  * enablement  — `enable(True)` / the REPRO_KERNELS env var;
  * dtype       — floats run natively; non-float inputs fall back (the
                  fp32 accumulator cannot guarantee exact integer sums);
  * rank        — kernels are 2-D; 1-D and >=2-D features are flattened to
                  [E, prod(feature_dims)] here and reshaped on exit;
  * VMEM budget — the fp32 accumulator (n_segments * D * 4B) plus one edge
                  block must fit `VMEM_BUDGET_BYTES`; `choose_e_block`
                  picks the largest power-of-two edge block that fits
                  instead of a hard-coded 256, and a block that cannot fit
                  at all routes the call to the reference;
  * layout      — `layout(sorted_by_target=True)` (set by runner.run from
                  BatchPlan.edges_sorted_by_target) plus per-call
                  `sorted_ids` hints select between the one-hot kernels
                  and the CSR-run variants: runs are preferred on sorted
                  streams (one run per segment) and serve as the VMEM
                  fallback for max/min shapes whose [E_blk, N, D]
                  broadcast never fit.  The hint is performance-only —
                  both variants are correct for any id order;
  * autotune    — with `use_autotune(True)` (REPRO_AUTOTUNE=1), a warmed
                  `results/autotune_cache.json` overrides the heuristic
                  (variant, e_block) per (shape, dtype, layout, backend)
                  key; lookups are pure dict reads, so steady state adds
                  zero recompiles;
  * backend     — off-TPU the kernel runs in interpret mode (semantics
                  checks, benchmarks); the jnp reference stays the oracle.

Contract shared by kernels and references: `seg_ids >= n_segments` mark
padding rows, and empty segments yield 0 for every reduction.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels.edge_mpnn import kernel as _mpnn_kernel
from repro.kernels.edge_mpnn.ref import edge_mpnn_ref
from repro.kernels.flash_attention import kernel as _flash_kernel
from repro.kernels.flash_attention.ref import segment_attention_ref
from repro.kernels.segment_pool import kernel as _seg_kernel
from repro.kernels.segment_pool.ref import segment_pool_ref

# ---------------------------------------------------------------------------
# Enablement (single source of truth; repro.core.ops delegates here)
# ---------------------------------------------------------------------------

_ENABLED = os.environ.get("REPRO_KERNELS", "0") == "1"


def enable(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# Partitioned tracing: eligibility must budget VMEM from PER-SHARD shapes.
#
# Two ways per-shard shapes reach the decision functions:
#
#   * shard_map step (repro.distributed.partition / graph_sharding): the
#     loss is traced with per-shard GraphTensors — leading dims split over
#     "data" by the in_specs, feature widths split over "model" by the
#     boundary ops in repro.core.ops — so `values.shape` is already the
#     per-shard shape and nothing else is needed; this is the default path;
#   * GSPMD auto-sharding over GLOBAL shapes (e.g. a pjit'd step whose batch
#     leaves keep the full super-batch dims at trace time): the step factory
#     must wrap tracing in the MeshPlan's `dispatch_context()` (i.e.
#     `with dispatch.partitioned(data=n, model=m):`) so row/segment counts
#     divide by the data shards and feature widths by the model shards.
#     Budgeting from global shapes would wrongly reject shard-sized work
#     ("exceeds VMEM") or pick edge blocks tuned for arrays 8x too large.
# ---------------------------------------------------------------------------

_DATA_SHARDS = 1
_MODEL_SHARDS = 1


@contextlib.contextmanager
def partitioned(data: int = 1, model: int = 1):
    """Trace-time context for the 2-D ("data", "model") mesh: decisions
    divide row/segment counts by `data` and feature widths by `model`.
    Only for steps traced with global batch shapes; the shard_map path
    sees per-shard shapes already and must not use this."""
    global _DATA_SHARDS, _MODEL_SHARDS
    prev = (_DATA_SHARDS, _MODEL_SHARDS)
    _DATA_SHARDS = max(int(data), 1)
    _MODEL_SHARDS = max(int(model), 1)
    try:
        yield
    finally:
        _DATA_SHARDS, _MODEL_SHARDS = prev


def data_parallel(num_shards: int):
    """The 1-D special case of :func:`partitioned` (kept for callers of
    the PR-2 data-only contract)."""
    return partitioned(data=num_shards)


def data_shards() -> int:
    return _DATA_SHARDS


def model_shards() -> int:
    return _MODEL_SHARDS


def _per_shard(n: int) -> int:
    """Per-shard count for a leading dim that GSPMD splits over data."""
    return -(-int(n) // _DATA_SHARDS)  # ceil: the largest shard decides


def _per_shard_feature(d: int) -> int:
    """Per-shard width for a feature dim split over the model axis (the
    boundary ops split only evenly-divisible widths; ceil covers the
    GSPMD-uneven case conservatively)."""
    return -(-int(d) // _MODEL_SHARDS)


# ---------------------------------------------------------------------------
# Layout hint: BatchPlan.edges_sorted_by_target, carried to trace time.
#
# The grouping layer sorts each merged batch's edges by (component, target)
# and appends padding rows last, so TARGET-tag segment ids arrive globally
# non-decreasing.  Decisions use the hint to prefer the CSR-run kernel
# variants (one contiguous run per segment).  It is ONLY a performance
# hint: the run kernels fold maximal stretches of equal consecutive ids,
# which is correct for any order, so a stale or wrong hint can never
# produce wrong results — just a slower variant choice.
# ---------------------------------------------------------------------------

_SORTED_BY_TARGET = False


@contextlib.contextmanager
def layout(sorted_by_target: bool = True):
    """Trace-time layout context (mirrors :func:`partitioned`): while
    active, TARGET-keyed reductions report their ids as sorted and
    dispatch prefers the CSR-run kernel variants."""
    global _SORTED_BY_TARGET
    prev = _SORTED_BY_TARGET
    _SORTED_BY_TARGET = bool(sorted_by_target)
    try:
        yield
    finally:
        _SORTED_BY_TARGET = prev


def layout_sorted_by_target() -> bool:
    return _SORTED_BY_TARGET


# ---------------------------------------------------------------------------
# Autotune consultation (results/autotune_cache.json; see kernels/autotune)
# ---------------------------------------------------------------------------

_AUTOTUNE = os.environ.get("REPRO_AUTOTUNE", "0") == "1"


def use_autotune(on: bool) -> None:
    """Let decisions consult the autotune cache.  Off by default so test
    and training dispatch stays independent of whatever cache file the
    working directory happens to contain."""
    global _AUTOTUNE
    _AUTOTUNE = bool(on)


def autotune_enabled() -> bool:
    return _AUTOTUNE


# ---------------------------------------------------------------------------
# VMEM budget model and block-size heuristic
# ---------------------------------------------------------------------------

# Half of a TPU core's ~16 MiB VMEM: leaves headroom for double-buffered
# input blocks and compiler temporaries.
VMEM_BUDGET_BYTES = 8 * 2 ** 20
MIN_E_BLOCK = 8          # fp32 sublane granularity
MAX_E_BLOCK = 1024       # beyond this the one-hot matmul dominates anyway
MAX_SEGMENTS = 4096      # one-hot lane dimension cap
MAX_FEATURE_DIM = 256    # flattened feature width cap

_SUPPORTED_REDUCES = ("sum", "mean", "max", "min")
_SUPPORTED_ACTIVATIONS = ("relu", "gelu", "identity")

# Declared worst-case operating envelopes, one (or more, keyed
# "kernel:variant") per registered kernel: the largest shapes each kernel
# is expected to DISPATCH for, i.e. its choose_* function must return a
# non-zero block there.  tools/repro_lint rule PAL002 re-evaluates these
# corners against the budget model statically (no jax import) and
# tests/test_dispatch.py asserts the dynamic decision agrees — so a
# budget-model edit that silently shrinks a kernel's reachable range
# fails lint instead of quietly benchmarking the reference.
#
# sum/mean run up to the full (MAX_SEGMENTS, MAX_FEATURE_DIM) cap; the
# ONE-HOT max/min variant additionally materialises the [E_blk, N, D]
# masked broadcast, which bounds its envelope to (2048, 64) — the CSR-run
# variant has no n_segments term per edge at all, so every reduce reaches
# the full cap there (the ":*_runs" corners below pin that).  The mpnn
# corner is the MAG-scale shape the Table-1 experiment dispatches: 4096
# nodes each side, 128-wide states and messages.  The graph_attention
# corner is the largest dense node-set batch the flash conv accepts.
WORST_CASE_ENVELOPES: dict[str, dict] = {
    "segment_pool:sum": dict(n_segments=MAX_SEGMENTS, d=MAX_FEATURE_DIM,
                             itemsize=4, reduce="sum"),
    "segment_pool:max": dict(n_segments=2048, d=64, itemsize=4,
                             reduce="max"),
    "segment_pool:min": dict(n_segments=2048, d=64, itemsize=4,
                             reduce="min"),
    "segment_pool:sum_runs": dict(n_segments=MAX_SEGMENTS,
                                  d=MAX_FEATURE_DIM, itemsize=4,
                                  reduce="sum", variant="runs"),
    "segment_pool:max_runs": dict(n_segments=MAX_SEGMENTS,
                                  d=MAX_FEATURE_DIM, itemsize=4,
                                  reduce="max", variant="runs"),
    "edge_mpnn": dict(n_src=MAX_SEGMENTS, n_tgt=MAX_SEGMENTS,
                      ds=128, dt=128, m=128, itemsize=4),
    "edge_mpnn:runs": dict(n_src=MAX_SEGMENTS, n_tgt=MAX_SEGMENTS,
                           ds=128, dt=128, m=128, itemsize=4,
                           variant="runs"),
    "graph_attention": dict(n_rows=MAX_SEGMENTS, num_heads=8,
                            head_dim=128, itemsize=4),
}


def _floor_pow2(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


def _ceil_pow2(x: int) -> int:
    return 1 << (max(int(x) - 1, 0).bit_length() if x > 1 else 0)


def _fit_block(resident: int, per_edge: int, n_edges: int | None) -> int:
    """Largest power-of-two edge block whose working set fits the budget."""
    avail = VMEM_BUDGET_BYTES - resident
    if avail < per_edge * MIN_E_BLOCK:
        return 0
    block = min(_floor_pow2(avail // per_edge), MAX_E_BLOCK)
    if n_edges is not None:
        block = min(block, max(_ceil_pow2(n_edges), MIN_E_BLOCK))
    return block


def choose_e_block(n_segments: int, d: int, itemsize: int = 4, *,
                   reduce: str = "sum", n_edges: int | None = None,
                   variant: str = "onehot") -> int:
    """Edge block for segment_pool; 0 means "does not fit, use reference".

    The envelope is split per variant: the one-hot kernel keeps an
    [E_blk, N] one-hot + [E_blk, D] values per step, and for max/min also
    the [E_blk, N, D] masked broadcast, which dominates.  The CSR-run
    variant replaces all of that with O(D)-per-edge scan state (fp32 scan
    rows + one shifted temp + the scratch copy) — no n_segments term, so
    max/min stop shrinking the block and large-N shapes keep dispatching.
    """
    resident = n_segments * d * 4  # fp32 accumulator
    if variant == "runs":
        per_edge = d * itemsize + 3 * d * 4 + 16
    else:
        per_edge = n_segments * itemsize + d * itemsize + 4
        if reduce in ("max", "min"):
            per_edge += n_segments * d * 4
    return _fit_block(resident, per_edge, n_edges)


def fits_budget(n_segments: int, d: int, itemsize: int = 4, *,
                reduce: str = "sum") -> bool:
    """Public budget query: would a segment reduction over `n_segments`
    targets with `d`-wide features stay inside the kernel envelope
    (dispatch caps + a non-zero edge block under `VMEM_BUDGET_BYTES`)?

    The serving bucket ladder (`repro.serve.gnn.build_ladder`) sizes its
    largest padded batch with this — buckets past the budget would silently
    demote every steady-state request to the reference path."""
    return (n_segments <= MAX_SEGMENTS and d <= MAX_FEATURE_DIM
            and choose_e_block(n_segments, d, itemsize, reduce=reduce) > 0)


def choose_mpnn_e_block(n_src: int, n_tgt: int, ds: int, dt: int, m: int,
                        itemsize: int = 4, *, n_edges: int | None = None,
                        variant: str = "onehot") -> int:
    """Edge block for the fused edge convolution; 0 means "does not fit".

    The CSR-run variant gathers with per-row dynamic loads and pools with
    a run scan, so its per-edge cost drops the n_src/n_tgt one-hot terms.
    It holds node states and gathered rows in fp32 whatever the input
    dtype (single-row dynamic access needs unpacked rows).
    """
    if variant == "runs":
        resident = (n_src * ds + n_tgt * dt) * 4 \
            + (ds + dt) * m * itemsize + n_tgt * m * 4  # fp32 accumulator
        per_edge = ((ds + dt) * (4 + itemsize)  # fp32 gathered rows + concat
                    + 2 * m * 4                 # fp32 message + scan temp
                    + 16)                       # edge ids
        return _fit_block(resident, per_edge, n_edges)
    resident = (n_src * ds + n_tgt * dt + (ds + dt) * m) * itemsize \
        + n_tgt * m * 4  # fp32 accumulator
    per_edge = (n_src * itemsize            # src one-hot
                + n_tgt * (itemsize + 4)    # tgt one-hot (+ fp32 copy)
                + 2 * (ds + dt) * itemsize  # gathered states + concat
                + m * 4                     # fp32 message row
                + 8)                        # edge ids
    return _fit_block(resident, per_edge, n_edges)


def choose_attention_block(n_rows: int, num_heads: int, head_dim: int,
                           itemsize: int = 4) -> int:
    """Square q/kv block for the segment-masked flash attention conv;
    0 means "does not fit".  Per grid step VMEM: the q/k/v blocks, the
    fp32 (m, l, acc) scratch, the [q_blk, kv_blk] logits/probs
    temporaries, and the two segment-id rows.  Heads ride the grid, so
    num_heads does not enter the per-step bytes."""
    if head_dim > MAX_FEATURE_DIM:
        return 0
    block = min(128, max(_ceil_pow2(n_rows), MIN_E_BLOCK))
    while block >= MIN_E_BLOCK:
        step_bytes = (3 * block * head_dim * itemsize
                      + block * (head_dim + 2) * 4
                      + 2 * block * block * 4
                      + 2 * block * 4)
        if step_bytes <= VMEM_BUDGET_BYTES:
            return block
        block //= 2
    return 0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Decision:
    """Outcome of an eligibility check: which path runs and why.
    `variant` names the kernel flavor ("onehot" / "runs" / "flash");
    it is meaningful only when use_kernel is True."""
    use_kernel: bool
    reason: str
    e_block: int = 0
    interpret: bool = False
    variant: str = "onehot"


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    name: str
    kernel: Callable          # Pallas path
    reference: Callable       # jnp oracle, identical contract
    decide: Callable          # (...) -> Decision


_REGISTRY: dict[str, KernelEntry] = {}


def register(entry: KernelEntry) -> None:
    _REGISTRY[entry.name] = entry


def registry() -> dict[str, KernelEntry]:
    return dict(_REGISTRY)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Autodiff: Pallas kernels have no JVP/transpose rules, so the kernel paths
# carry a custom VJP whose backward pass is the jnp reference's — forward
# runs fused, gradients are the reference's exactly (at the cost of one
# reference forward recompute on the backward pass).
# ---------------------------------------------------------------------------

def _seg_kernel_with_ref_vjp(flat, seg_ids, *, n_segments, reduce, e_block,
                             interpret, variant="onehot"):
    kernel_fn = (_seg_kernel.segment_pool_runs if variant == "runs"
                 else _seg_kernel.segment_pool)

    @jax.custom_vjp
    def run(v):
        return kernel_fn(v, seg_ids, n_segments=n_segments,
                         reduce=reduce, e_block=e_block,
                         interpret=interpret)

    def fwd(v):
        return run(v), v

    def bwd(v, g):
        _, vjp = jax.vjp(
            lambda vv: segment_pool_ref(vv, seg_ids, n_segments=n_segments,
                                        reduce=reduce), v)
        return vjp(g)

    run.defvjp(fwd, bwd)
    return run(flat)


def _mpnn_kernel_with_ref_vjp(h_src, h_tgt, src, tgt, w, b, *, n_src,
                              n_tgt, e_block, activation, interpret,
                              variant="onehot"):
    kernel_fn = (_mpnn_kernel.edge_mpnn_runs if variant == "runs"
                 else _mpnn_kernel.edge_mpnn)

    @jax.custom_vjp
    def run(hs, ht, ww, bb):
        return kernel_fn(hs, ht, src, tgt, ww, bb,
                         n_src=n_src, n_tgt=n_tgt,
                         e_block=e_block,
                         activation=activation,
                         interpret=interpret)

    def fwd(hs, ht, ww, bb):
        return run(hs, ht, ww, bb), (hs, ht, ww, bb)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda hs, ht, ww, bb: edge_mpnn_ref(
                hs, ht, src, tgt, ww, bb, n_src=n_src, n_tgt=n_tgt,
                activation=activation), *res)
        return vjp(g)

    run.defvjp(fwd, bwd)
    return run(h_src, h_tgt, w, b)


# ---------------------------------------------------------------------------
# segment_reduce: sum / mean / max / min over segments
# ---------------------------------------------------------------------------

def segment_reduce_decision(shape: tuple, dtype, n_segments: int,
                            reduce: str = "sum",
                            sorted_ids: bool | None = None) -> Decision:
    """Eligibility + variant choice for one segment reduction (shape =
    values.shape).  sorted_ids=None reads the ambient `layout()` hint."""
    if reduce not in _SUPPORTED_REDUCES:
        return Decision(False, f"unsupported reduce {reduce!r}")
    if not _ENABLED:
        return Decision(False, "kernels disabled")
    if shape[0] == 0:
        return Decision(False, "no rows (empty grid)")
    if sorted_ids is None:
        sorted_ids = _SORTED_BY_TARGET
    base = "sum" if reduce == "mean" else reduce
    d = 1
    for dim in shape[1:]:
        d *= int(dim)
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        # Integer sums cannot run via the fp32 accumulator: exactness
        # depends on value magnitude, which is unknown at trace time
        # (counting callers like node_degree sum fp32 ones instead).
        return Decision(False, f"non-float dtype {dtype} routes to "
                        "reference")
    itemsize = dtype.itemsize
    # Per-device counts: under partitioned(data=n, model=m) the trace-time
    # shapes are global; one device owns ~1/n of the rows/segments and
    # ~1/m of the feature width.
    n_rows = _per_shard(shape[0])
    n_seg = _per_shard(n_segments)
    d = _per_shard_feature(d)
    sharded = f" (per-shard of {_DATA_SHARDS} data shards)" \
        if _DATA_SHARDS > 1 else ""
    if _MODEL_SHARDS > 1:
        sharded += f" (per-shard of {_MODEL_SHARDS} model shards)"
    if n_seg > MAX_SEGMENTS:
        return Decision(False,
                        f"n_segments {n_seg}{sharded} > {MAX_SEGMENTS}")
    if d > MAX_FEATURE_DIM:
        return Decision(False, f"feature width {d} > {MAX_FEATURE_DIM}")
    layout_name = "sorted" if sorted_ids else "unsorted"
    if _AUTOTUNE:
        from repro.kernels import autotune as _autotune
        rec = _autotune.lookup(_autotune.cache_key(
            "segment_pool", n=n_seg, d=d, dtype=str(dtype), reduce=base,
            layout=layout_name, backend=jax.default_backend()))
        if rec:
            cap = choose_e_block(n_seg, d, itemsize, reduce=base,
                                 variant=rec.get("variant", "onehot"))
            if MIN_E_BLOCK <= int(rec.get("e_block", 0)) <= cap:
                return Decision(
                    True, f"autotuned:{rec['variant']}{sharded}",
                    int(rec["e_block"]), interpret=not _on_tpu(),
                    variant=rec["variant"])
    # Heuristic: CSR-run first on sorted streams (one run per segment);
    # one-hot first otherwise (MXU-shaped).  Either way the other variant
    # is the VMEM fallback — notably max/min at large N, where only the
    # run variant fits.
    order = ("runs", "onehot") if sorted_ids else ("onehot", "runs")
    for variant in order:
        e_block = choose_e_block(n_seg, d, itemsize, reduce=base,
                                 n_edges=n_rows, variant=variant)
        if e_block:
            return Decision(True, f"kernel:{variant}[{layout_name}]"
                            f"{sharded}", e_block,
                            interpret=not _on_tpu(), variant=variant)
    return Decision(False, "working set exceeds VMEM budget for both "
                    f"variants{sharded}")


def segment_reduce(values, seg_ids, n_segments: int, reduce: str = "sum",
                   *, sorted_ids: bool | None = None):
    """Route one segment reduction to the Pallas kernel or jnp reference.

    values: [E, ...]; seg_ids: [E] with >= n_segments marking padding rows.
    Returns [n_segments, ...]; empty segments yield 0; mean divides by
    max(count, 1) where count is the number of non-padding rows.
    sorted_ids hints that seg_ids arrive non-decreasing (performance only;
    None defers to the ambient `layout()` context).
    """
    if reduce == "mean":
        total = segment_reduce(values, seg_ids, n_segments, "sum",
                               sorted_ids=sorted_ids)
        cnt = segment_count(seg_ids, n_segments)
        cnt = cnt.reshape(cnt.shape + (1,) * (values.ndim - 1))
        out_dtype = (total.dtype
                     if jnp.issubdtype(total.dtype, jnp.floating)
                     else jnp.float32)
        # divide in fp32: a bf16 count would saturate at 256
        return (total.astype(jnp.float32)
                / jnp.maximum(cnt, 1)).astype(out_dtype)
    entry = _REGISTRY["segment_pool"]
    dec = entry.decide(values.shape, values.dtype, n_segments, reduce,
                       sorted_ids)
    if not dec.use_kernel:
        return entry.reference(values, seg_ids, n_segments=n_segments,
                               reduce=reduce)
    flat = values.reshape(values.shape[0], -1)
    out = _seg_kernel_with_ref_vjp(flat, seg_ids, n_segments=n_segments,
                                   reduce=reduce, e_block=dec.e_block,
                                   interpret=dec.interpret,
                                   variant=dec.variant)
    return out.reshape((n_segments,) + values.shape[1:])


def segment_count(seg_ids, n_segments: int, dtype=jnp.float32):
    """Rows per segment (padding ids >= n_segments excluded).

    Counting is always an O(E) plain segment_sum: the one-hot kernel would
    spend O(E * n_segments) MXU work to count rows, so this path is never
    kernel-eligible by design (used by mean pooling and node_degree).
    Pass an integer dtype for exact counts beyond 2**24.
    """
    valid = seg_ids < n_segments
    return jax.ops.segment_sum(valid.astype(dtype),
                               jnp.where(valid, seg_ids, n_segments),
                               num_segments=n_segments + 1)[:n_segments]


# ---------------------------------------------------------------------------
# edge_mpnn: fused gather -> per-edge MLP message -> segment-sum
# ---------------------------------------------------------------------------

def edge_mpnn_decision(n_src: int, n_tgt: int, ds: int, dt: int, m: int,
                       dtype, activation: str = "relu",
                       n_edges: int | None = None,
                       sorted_ids: bool | None = None) -> Decision:
    if activation not in _SUPPORTED_ACTIVATIONS:
        return Decision(False, f"unsupported activation {activation!r}")
    if not _ENABLED:
        return Decision(False, "kernels disabled")
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return Decision(False, f"unsupported dtype {dtype}")
    if n_edges == 0:
        return Decision(False, "no edges (empty grid)")
    if sorted_ids is None:
        sorted_ids = _SORTED_BY_TARGET
    n_src_s, n_tgt_s = _per_shard(n_src), _per_shard(n_tgt)
    sharded = f" (per-shard of {_DATA_SHARDS} data shards)" \
        if _DATA_SHARDS > 1 else ""
    if max(n_src_s, n_tgt_s) > MAX_SEGMENTS:
        return Decision(False, f"node count{sharded} > {MAX_SEGMENTS}")
    if m > MAX_FEATURE_DIM:
        return Decision(False, f"message width {m} > {MAX_FEATURE_DIM}")
    n_edges_s = None if n_edges is None else _per_shard(n_edges)
    layout_name = "sorted" if sorted_ids else "unsorted"
    if _AUTOTUNE:
        from repro.kernels import autotune as _autotune
        rec = _autotune.lookup(_autotune.cache_key(
            "edge_mpnn", n_src=n_src_s, n_tgt=n_tgt_s, ds=ds, dt=dt, m=m,
            dtype=str(dtype), activation=activation, layout=layout_name,
            backend=jax.default_backend()))
        if rec:
            cap = choose_mpnn_e_block(n_src_s, n_tgt_s, ds, dt, m,
                                      dtype.itemsize,
                                      variant=rec.get("variant", "onehot"))
            if MIN_E_BLOCK <= int(rec.get("e_block", 0)) <= cap:
                return Decision(
                    True, f"autotuned:{rec['variant']}{sharded}",
                    int(rec["e_block"]), interpret=not _on_tpu(),
                    variant=rec["variant"])
    order = ("runs", "onehot") if sorted_ids else ("onehot", "runs")
    for variant in order:
        e_block = choose_mpnn_e_block(n_src_s, n_tgt_s, ds, dt, m,
                                      dtype.itemsize, n_edges=n_edges_s,
                                      variant=variant)
        if e_block:
            return Decision(True, f"kernel:{variant}[{layout_name}]"
                            f"{sharded}", e_block,
                            interpret=not _on_tpu(), variant=variant)
    return Decision(False, "working set exceeds VMEM budget for both "
                    f"variants{sharded}")


def edge_mpnn(h_src, h_tgt, src, tgt, w, b, *, n_src: int, n_tgt: int,
              activation: str = "relu", sorted_ids: bool | None = None):
    """Fused edge convolution (or its jnp reference when ineligible).

    h_src: [n_src, Ds]; h_tgt: [n_tgt, Dt]; src/tgt: [E] with padding edges
    carrying tgt >= n_tgt; w: [Ds+Dt, M]; b: [M].  Returns [n_tgt, M].
    sorted_ids hints that tgt arrives non-decreasing (performance only).
    """
    entry = _REGISTRY["edge_mpnn"]
    dec = entry.decide(n_src, n_tgt, h_src.shape[1], h_tgt.shape[1],
                       w.shape[1], h_src.dtype, activation,
                       n_edges=int(src.shape[0]), sorted_ids=sorted_ids)
    if not dec.use_kernel:
        return entry.reference(h_src, h_tgt, src, tgt, w, b, n_src=n_src,
                               n_tgt=n_tgt, activation=activation)
    return _mpnn_kernel_with_ref_vjp(h_src, h_tgt, src, tgt, w, b,
                                     n_src=n_src, n_tgt=n_tgt,
                                     e_block=dec.e_block,
                                     activation=activation,
                                     interpret=dec.interpret,
                                     variant=dec.variant)


# ---------------------------------------------------------------------------
# graph_attention: dense within-component multi-head attention over a node
# set, backed by the segment-masked flash attention kernel
# ---------------------------------------------------------------------------

def graph_attention_decision(n_rows: int, num_heads: int, head_dim: int,
                             dtype) -> Decision:
    """Eligibility for the flash graph-attention conv.  The kernel runs
    one [N, H, Dh] node set as a single segment-masked sequence, so the
    caps are on padded node count and head width; components never enter
    the budget (the mask is free)."""
    if not _ENABLED:
        return Decision(False, "kernels disabled")
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return Decision(False, f"unsupported dtype {dtype}")
    if n_rows == 0:
        return Decision(False, "no rows (empty grid)")
    n = _per_shard(n_rows)
    sharded = f" (per-shard of {_DATA_SHARDS} data shards)" \
        if _DATA_SHARDS > 1 else ""
    if n > MAX_SEGMENTS:
        return Decision(False, f"node count {n}{sharded} > {MAX_SEGMENTS}")
    block = choose_attention_block(n, num_heads, head_dim, dtype.itemsize)
    if block == 0:
        return Decision(False,
                        f"working set exceeds VMEM budget{sharded}")
    return Decision(True, f"kernel:flash{sharded}", block,
                    interpret=not _on_tpu(), variant="flash")


def _flash_graph_attention(q, k, v, segments, *, block, interpret):
    """[N, H, Dh] q/k/v + [N] segment ids -> [N, H, Dh] via the flash
    kernel.  Pads N to a block multiple with MISMATCHING sentinel segment
    ids (-1 queries vs -2 keys): padded queries match no key, so the
    kernel's l=0 guard emits exact zeros for them and the slice below
    drops nothing real."""
    n = q.shape[0]
    pad = (-n) % block
    if pad:
        widths = ((0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, widths) for a in (q, k, v))
        q_seg = jnp.pad(segments, (0, pad), constant_values=-1)
        kv_seg = jnp.pad(segments, (0, pad), constant_values=-2)
    else:
        q_seg = kv_seg = segments
    out = _flash_kernel.flash_attention(
        q[None], k[None], v[None], q_seg[None], kv_seg[None],
        causal=False, q_block=block, kv_block=block, interpret=interpret)
    return out[0, :n]


def _attention_kernel_with_ref_vjp(q, k, v, segments, *, block, interpret):
    @jax.custom_vjp
    def run(qq, kk, vv):
        return _flash_graph_attention(qq, kk, vv, segments, block=block,
                                      interpret=interpret)

    def fwd(qq, kk, vv):
        return run(qq, kk, vv), (qq, kk, vv)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda qq, kk, vv: segment_attention_ref(qq, kk, vv, segments),
            *res)
        return vjp(g)

    run.defvjp(fwd, bwd)
    return run(q, k, v)


def graph_attention(q, k, v, segments):
    """Within-component softmax attention (or its einsum reference when
    ineligible).

    q/k/v: [N, H, Dh]; segments: [N] int32 component ids with padding rows
    carrying the one-past-last component id (component_ids() gives this
    for free).  Returns [N, H, Dh]; a row attends exactly to the rows of
    its own component (padding rows attend among themselves and are
    discarded by downstream masks).
    """
    n, h, dh = q.shape
    entry = _REGISTRY["graph_attention"]
    dec = entry.decide(n, h, dh, q.dtype)
    if not dec.use_kernel:
        return entry.reference(q, k, v, segments)
    return _attention_kernel_with_ref_vjp(q, k, v, segments,
                                          block=dec.e_block,
                                          interpret=dec.interpret)


register(KernelEntry("segment_pool", _seg_kernel.segment_pool,
                     segment_pool_ref, segment_reduce_decision))
register(KernelEntry("edge_mpnn", _mpnn_kernel.edge_mpnn, edge_mpnn_ref,
                     edge_mpnn_decision))
register(KernelEntry("graph_attention", _flash_kernel.flash_attention,
                     segment_attention_ref, graph_attention_decision))
