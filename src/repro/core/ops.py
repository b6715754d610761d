"""Data-exchange ops (paper §4.1, API Level 2).

Broadcast and pool between node sets, edge sets and context.  All ops work
on the fixed-capacity GraphTensor: padding items are masked out of every
reduction, so results over valid items match the ragged semantics of the
paper exactly (tested in tests/test_ops.py against a dense-adjacency
oracle).

Index-based exchange (gather/segment ops) is the paper's core design choice
vs. adjacency matmuls.  Every segment-shaped reduction below routes through
`repro.kernels.dispatch`, the single registry/eligibility layer that picks
the Pallas TPU kernel or the jnp reference per call site; enable the kernel
path via `use_kernels(True)` or the REPRO_KERNELS env var.  Padding is
expressed uniformly by remapping padded rows' segment ids to `n_segments`
(the dispatch contract: out-of-range ids are dropped, empty segments
yield 0).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import mp_context
from repro.core.graph_tensor import (CONTEXT, GraphTensor, HIDDEN_STATE,
                                     SOURCE, TARGET)
from repro.kernels import dispatch as kernel_dispatch

_REDUCE_TYPES = ("sum", "mean", "max", "min")


def _scoped(name: str):
    """Runs the op under `jax.named_scope(name)`, so a trace of the
    compiled program can tell ``pool`` and ``broadcast`` time apart."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# Feature-dim model parallelism (driven by the MeshPlan of
# repro.distributed.partition through repro.core.mp_context).
#
# Inside a model-parallel shard_map body the segment reductions at the
# broadcast/pool exchange boundary split the trailing feature axis over
# the "model" mesh axis: the reduction runs on this device's feature
# chunk (so kernel dispatch budgets VMEM from the per-shard width) and
# the pooled result is all-gathered back to full width — the one
# cross-device exchange of the model-parallel contract.  Broadcast
# (`jnp.take`) needs no collective: its input is already full width
# (gathered at step entry / at the previous pool exit) and a gather of a
# replicated value is communication-free.
#
# Chunks are exact slices, reductions are feature-independent and the
# gather concatenates them in mesh order, so results are bit-identical to
# the unsharded path at any model_parallel factor.  Widths the model axis
# does not divide fall back to the unsharded op.
# ---------------------------------------------------------------------------

def _mp_segment_reduce(value, seg_ids, n_segments, reduce_type,
                       sorted_ids=None):
    """Segment reduction with the feature axis split over the model mesh
    axis (all-gather at the pool boundary); unsharded outside a
    model-parallel trace context.  sorted_ids is the layout hint for
    dispatch (None defers to the ambient `dispatch.layout()` context;
    performance-only, never correctness)."""
    ctx = mp_context.current_model_context()
    if ctx is not None and ctx.can_split(value):
        out = kernel_dispatch.segment_reduce(ctx.split(value), seg_ids,
                                             n_segments, reduce_type,
                                             sorted_ids=sorted_ids)
        return ctx.gather(out)
    return kernel_dispatch.segment_reduce(value, seg_ids, n_segments,
                                          reduce_type,
                                          sorted_ids=sorted_ids)


def use_kernels(enabled: bool) -> None:
    kernel_dispatch.enable(enabled)


def kernels_enabled() -> bool:
    return kernel_dispatch.enabled()


def _edge_endpoint(graph: GraphTensor, edge_set_name: str, tag: str):
    es = graph.edge_sets[edge_set_name]
    adj = es.adjacency
    if tag == SOURCE:
        return adj.source, adj.source_name
    if tag == TARGET:
        return adj.target, adj.target_name
    raise ValueError(f"tag must be SOURCE or TARGET, got {tag!r}")


def _resolve_feature(piece, feature_name, feature_value):
    if (feature_name is None) == (feature_value is None):
        raise ValueError("exactly one of feature_name/feature_value required")
    return piece[feature_name] if feature_name is not None else feature_value


# ---------------------------------------------------------------------------
# node <-> edge
# ---------------------------------------------------------------------------

@_scoped("broadcast")
def broadcast_node_to_edges(graph: GraphTensor, edge_set_name: str, tag: str,
                            *, feature_name: str | None = None,
                            feature_value=None):
    """For each edge, the feature value at its `tag` endpoint node."""
    idx, node_set_name = _edge_endpoint(graph, edge_set_name, tag)
    value = _resolve_feature(graph.node_sets[node_set_name], feature_name,
                             feature_value)
    return jnp.take(value, idx, axis=0)


@_scoped("pool")
def pool_edges_to_node(graph: GraphTensor, edge_set_name: str, tag: str,
                       reduce_type: str = "sum", *,
                       feature_name: str | None = None, feature_value=None):
    """Aggregate per-edge values at each `tag` endpoint node (paper Eq. 3).

    Padding edges are excluded; nodes with no (valid) incident edges
    yield 0 for every reduce_type.
    """
    if reduce_type not in _REDUCE_TYPES:
        raise ValueError(f"unknown reduce_type {reduce_type!r}")
    es = graph.edge_sets[edge_set_name]
    idx, node_set_name = _edge_endpoint(graph, edge_set_name, tag)
    value = _resolve_feature(es, feature_name, feature_value)
    num_nodes = graph.node_sets[node_set_name].capacity
    seg_ids = jnp.where(es.mask(), idx, num_nodes)  # padding -> dropped
    # BatchPlan sorts edges by (component, target) and pads last, so
    # TARGET-keyed ids are non-decreasing exactly when the ambient
    # dispatch.layout() hint says so; SOURCE-keyed ids never are.
    return _mp_segment_reduce(value, seg_ids, num_nodes, reduce_type,
                              sorted_ids=None if tag == TARGET else False)


@_scoped("pool")
def segment_softmax(graph: GraphTensor, edge_set_name, tag: str,
                    *, feature_value):
    """Softmax of per-edge scores within each receiver node's edge segment
    (the attention-pooling primitive used by GATv2/transformer convs).

    `edge_set_name` may also be a sequence of edge sets whose `tag`
    endpoints are one node set, with `feature_value` a sequence of their
    scores: then each receiver's softmax runs over its edges in all of
    them together (one max and one sum over the union, as HGT's Eq. 3),
    and one coefficient array per edge set is returned, in order."""
    if isinstance(edge_set_name, str):
        return _joint_softmax(graph, [edge_set_name], tag,
                              [feature_value])[0]
    return _joint_softmax(graph, list(edge_set_name), tag,
                          list(feature_value))


def _joint_softmax(graph: GraphTensor, names: list, tag: str,
                   values: list) -> list:
    ends = [_edge_endpoint(graph, name, tag) for name in names]
    node_sets = {ns for _, ns in ends}
    if len(node_sets) != 1:
        raise ValueError(f"edge sets {names} have {tag} endpoints in "
                         f"{sorted(node_sets)}, not in one node set")
    num_nodes = graph.node_sets[ends[0][1]].capacity
    masks = [graph.edge_sets[name].mask() for name in names]
    masks_b = [m.reshape(m.shape + (1,) * (v.ndim - 1))
               for m, v in zip(masks, values)]
    ids = [idx for idx, _ in ends]
    seg_ids = [jnp.where(m, idx, num_nodes) for m, idx in zip(masks, ids)]
    # one edge set keeps its layout hint; edges of several are not sorted
    sorted_ids = None if tag == TARGET and len(names) == 1 else False

    def reduce(parts, reduce_type):
        if len(parts) == 1:
            return _mp_segment_reduce(parts[0], seg_ids[0], num_nodes,
                                      reduce_type, sorted_ids=sorted_ids)
        return _mp_segment_reduce(
            jnp.concatenate(parts), jnp.concatenate(seg_ids), num_nodes,
            reduce_type, sorted_ids=sorted_ids)

    # max-shift for stability, then exp-sum — both dispatched reductions
    # (feature-split over the model axis inside a model-parallel trace)
    seg_max = reduce(values, "max")
    exps = []
    for v, mb, idx in zip(values, masks_b, ids):
        shifted = jnp.where(mb, v - jnp.take(seg_max, idx, axis=0),
                            -jnp.inf)
        exps.append(jnp.where(mb, jnp.exp(shifted), 0))
    seg_sum = reduce(exps, "sum")
    return [exp / jnp.maximum(jnp.take(seg_sum, idx, axis=0), 1e-37)
            for exp, idx in zip(exps, ids)]


# ---------------------------------------------------------------------------
# context <-> node/edge
# ---------------------------------------------------------------------------

def _piece(graph: GraphTensor, name: str, node_or_edge: str):
    return (graph.node_sets[name] if node_or_edge == "node"
            else graph.edge_sets[name])


@_scoped("broadcast")
def broadcast_context_to_nodes(graph: GraphTensor, node_set_name: str, *,
                               feature_name: str | None = None,
                               feature_value=None):
    value = _resolve_feature(graph.context, feature_name, feature_value)
    comp = graph.node_sets[node_set_name].component_ids()
    return jnp.take(value, jnp.minimum(comp, value.shape[0] - 1), axis=0)


@_scoped("broadcast")
def broadcast_context_to_edges(graph: GraphTensor, edge_set_name: str, *,
                               feature_name: str | None = None,
                               feature_value=None):
    value = _resolve_feature(graph.context, feature_name, feature_value)
    comp = graph.edge_sets[edge_set_name].component_ids()
    return jnp.take(value, jnp.minimum(comp, value.shape[0] - 1), axis=0)


@_scoped("pool")
def _pool_items_to_context(piece, num_components, reduce_type, value):
    if reduce_type not in _REDUCE_TYPES:
        raise ValueError(f"unknown reduce_type {reduce_type!r}")
    comp = jnp.where(piece.mask(), piece.component_ids(),
                     num_components)  # padding -> dropped
    # component_ids is non-decreasing by construction (searchsorted over
    # the cumulative sizes) and padding rows map to num_components at the
    # end, so context pooling is always run-sorted
    return _mp_segment_reduce(value, comp, num_components, reduce_type,
                              sorted_ids=True)


def pool_nodes_to_context(graph: GraphTensor, node_set_name: str,
                          reduce_type: str = "sum", *,
                          feature_name: str | None = None,
                          feature_value=None):
    """Aggregate node values per graph component."""
    ns = graph.node_sets[node_set_name]
    value = _resolve_feature(ns, feature_name, feature_value)
    return _pool_items_to_context(ns, graph.num_components, reduce_type,
                                  value)


def pool_edges_to_context(graph: GraphTensor, edge_set_name: str,
                          reduce_type: str = "sum", *,
                          feature_name: str | None = None,
                          feature_value=None):
    es = graph.edge_sets[edge_set_name]
    value = _resolve_feature(es, feature_name, feature_value)
    return _pool_items_to_context(es, graph.num_components, reduce_type,
                                  value)


@_scoped("pool")
def node_degree(graph: GraphTensor, edge_set_name: str, tag: str):
    """Valid-edge degree of each node at endpoint `tag`."""
    es = graph.edge_sets[edge_set_name]
    idx, node_set_name = _edge_endpoint(graph, edge_set_name, tag)
    num_nodes = graph.node_sets[node_set_name].capacity
    seg_ids = jnp.where(es.mask(), idx, num_nodes)
    # int32 count: exact for any degree (fp32 would stop at 2**24)
    return kernel_dispatch.segment_count(seg_ids, num_nodes,
                                         dtype=jnp.int32)
