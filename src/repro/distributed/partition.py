"""Unified 2-D ("data", "model") partitioning: one MeshPlan for every layer.

This module collapses the partitioning logic that used to be scattered over
`distributed/sharding.py` rule lookups, `distributed/graph_sharding.py`
data-only NamedShardings, per-call-site shard_map plumbing in
`train/train_loop.py`, and the ad-hoc `data_parallel(n)` VMEM budgeting in
`kernels/dispatch.py` into one subsystem.  A :class:`MeshPlan` owns the
mesh, the logical-axis rule tables, and derives

* **per-leaf placement specs** for GraphTensor super-batches: the leading
  component-group axis resolves to the mesh's data axes (logical
  ``"batch"``) and the *trailing feature axes* of rank>=3 leaves resolve to
  ``"model"`` (logical ``"feature"``) via the same `DEFAULT_ACT_RULES`
  every other layer uses — so `put_super_batch` / `device_prefetch` land
  batches with the correct 2-D sharding and the train step's `shard_map`
  in_specs match placement exactly (no resharding copy on the first step);

* **the gather/psum boundaries**: inside a train-step body the model axis
  is made visible to `repro.core.ops` through a trace-time
  :func:`model_parallel_trace` context; the ops split the feature axis and
  insert the cross-device all-gather exactly at the
  `broadcast_node_to_edges` / `pool_edges_to_node` boundary, so segment
  reductions (and `repro.kernels.dispatch` eligibility / e_block budgets)
  see per-shard feature widths.  Gradients are `pmean`'d over *all* mesh
  axes: over "data" that is the cross-replica reduction, over "model" it
  reassembles the per-chunk parameter cotangents produced by the split
  boundaries (exact — chunks have disjoint support);

* **ZeRO-1 sharded optimizer state**: `AdamWState` / `AdafactorState`
  leaves are sharded over "data" via the optimizers' existing
  `state_axes` (logical ``"embed"`` -> "data", the same FSDP rule the
  transformer stack uses).  Each data shard updates only its slice of the
  parameters (`zero_slice`), the optimizer's `global_norm` /
  `clip_by_global_norm` are psum-corrected over the data axes, and the
  updated parameter slices are all-gathered — params stay replicated,
  optimizer state shrinks by the data-parallel factor.

A (data=1, model=1) plan runs the identical program shape as the PR-2
1-D path and trains to the same loss (`tests/test_graph_sharding.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.mp_context import (ModelContext,  # noqa: F401 (API)
                                   current_model_context,
                                   model_parallel_trace)
from repro.distributed.sharding import (DEFAULT_ACT_RULES,
                                        DEFAULT_PARAM_RULES, ShardingContext,
                                        data_axis_names, is_axes_leaf)


GROUP_AXIS = "batch"    # logical name of the leading component-group axis
FEATURE_AXIS = "feature"  # logical name of a trailing feature axis
MODEL_AXIS = "model"    # mesh axis carrying feature-dim model parallelism


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> bool:
    """Join a multi-process (multi-host) jax job.

    Reads explicit args or the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment (the
    contract the ``examples/ogbn_mag_train.py --multihost`` launcher
    exports to its children).  Must run before the first computation /
    device query, like `jax.distributed.initialize` itself.  Returns
    True when a multi-process runtime was initialized, False when
    unconfigured or world size is 1 (single-process runs need nothing).

    After this, `jax.devices()` is the GLOBAL device list, `make_mesh`
    builds a global mesh, and every `MeshPlan` placement routes host
    data through the process-local assembly path
    (`jax.make_array_from_process_local_data` for per-rank batches,
    callback-based placement for host-replicated state).
    """
    import os
    coord = coordinator_address or os.environ.get("REPRO_COORDINATOR", "")
    nproc = int(num_processes if num_processes is not None
                else os.environ.get("REPRO_NUM_PROCESSES", "0") or 0)
    pid = int(process_id if process_id is not None
              else os.environ.get("REPRO_PROCESS_ID", "0") or 0)
    if not coord or nproc <= 1:
        return False
    # CPU cross-process collectives (the CI / test backend) need the gloo
    # implementation
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    return True


def make_mesh(num_devices: Optional[int] = None, *,
              model_parallel: int = 1) -> Mesh:
    """A ("data",) mesh, or a 2-D ("data", "model") mesh when
    ``model_parallel > 1`` (data rows x model columns).

    Under `jax.distributed` the mesh is GLOBAL: `jax.devices()` is
    process-major (every process's local devices are one contiguous
    block), so the "data" axis tiles processes in rank order — global
    component group ``g`` lands on the same global data row as in a
    single-process run of the same mesh size, which is what makes the
    multi-process loss bit-compatible.  Model columns must stay inside
    one process (feature chunks of one group never cross hosts)."""
    devices = jax.devices()
    n = num_devices or len(devices)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — on CPU run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    mp = max(int(model_parallel), 1)
    if n % mp:
        raise ValueError(f"model_parallel {mp} must divide the device "
                         f"count {n}")
    if jax.process_count() > 1:
        if n != len(devices):
            raise ValueError(
                f"multi-process meshes must span every global device "
                f"(asked for {n} of {len(devices)}): each process has to "
                "contribute its addressable shard")
        if jax.local_device_count() % mp:
            raise ValueError(
                f"model_parallel {mp} must divide the "
                f"{jax.local_device_count()} local devices — model "
                "columns cannot cross process boundaries")
    devs = np.asarray(devices[:n])
    if mp == 1:
        return Mesh(devs, ("data",))
    return Mesh(devs.reshape(n // mp, mp), ("data", MODEL_AXIS))


# Trace-time model-parallel context: owned by repro.core.mp_context (a
# dependency-free core-layer module, so repro.core.ops reads it without
# importing this package); re-exported above as the plan's API surface.


# ---------------------------------------------------------------------------
# MeshPlan
# ---------------------------------------------------------------------------

def _leaf_axes(x):
    """Logical axes of one super-batch leaf: the leading group axis is
    "batch"; the trailing dim of rank>=3 leaves (node/edge/context
    features with a real feature dim) is "feature".  Rank<=2 leaves
    (sizes, adjacency, scalar features — whose last dim is the item
    capacity) never resolve to "model"."""
    if x.ndim >= 3:
        return (GROUP_AXIS,) + (None,) * (x.ndim - 2) + (FEATURE_AXIS,)
    return (GROUP_AXIS,) + (None,) * (x.ndim - 1)


def graph_logical_axes(graph):
    """Logical-axes tree for a stacked super-batch (see `_leaf_axes`)."""
    return jax.tree_util.tree_map(_leaf_axes, graph)


_SPEC_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Axes, per-leaf specs and gather/psum boundaries for one mesh.

    Every layer consumes the plan instead of re-deriving its own specs:
    `graph_specs`/`graph_shardings` (placement + shard_map in_specs),
    `zero_*` (optimizer-state layout), `model_context` (the ops-level
    gather boundary), `dispatch_context` (per-shard VMEM budgets for
    steps traced with global shapes)."""

    mesh: Mesh
    param_rules: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_PARAM_RULES))
    act_rules: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_ACT_RULES))

    # -- axis bookkeeping ----------------------------------------------------

    @property
    def data_axes(self) -> tuple:
        return data_axis_names(self.mesh)

    @property
    def data_size(self) -> int:
        size = 1
        for a in self.data_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def model_axis(self) -> Optional[str]:
        if MODEL_AXIS in self.mesh.axis_names \
                and self.mesh.shape[MODEL_AXIS] > 1:
            return MODEL_AXIS
        return None

    @property
    def model_size(self) -> int:
        return self.mesh.shape.get(MODEL_AXIS, 1)

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    # -- multi-process (jax.distributed) bookkeeping -------------------------

    @property
    def is_multiprocess(self) -> bool:
        """True when the mesh spans devices this process cannot address
        (a `jax.distributed` global mesh) — every host->device placement
        then assembles global arrays from process-local data instead of
        `device_put`."""
        me = jax.process_index()
        return any(d.process_index != me for d in self.mesh.devices.flat)

    @property
    def process_count(self) -> int:
        """Processes contributing devices to this mesh (1 == all local)."""
        return len({d.process_index for d in self.mesh.devices.flat})

    @property
    def local_data_size(self) -> int:
        """Data shards whose devices THIS process owns — the divisor for
        a process-local super-batch's group count.  Single-process: the
        full data size."""
        if not self.is_multiprocess:
            return self.data_size
        if self.data_size % self.process_count:
            raise ValueError(
                f"data size {self.data_size} not divisible by the "
                f"{self.process_count} participating processes")
        return self.data_size // self.process_count

    def _host_put(self, x, spec):
        """Host value (identical on every process) -> placed array.
        Single-process: `device_put`.  Multi-process: assemble the global
        array from a callback that serves each addressable device its
        slice of the full host value (works for replicated params AND
        data-sharded ZeRO-1 optimizer state — `opt.init` runs identically
        on every process, so the full value is available everywhere)."""
        sharding = NamedSharding(self.mesh, spec)
        if not self.is_multiprocess:
            return jax.device_put(x, sharding)
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    def _scaled_graph_specs(self, graph):
        """Specs for a multi-process super-batch, resolved against GLOBAL
        leaf shapes (local leading group dim x process_count): the
        divisibility fixup must see the global batch, or it would
        'helpfully' replicate every leaf whose local group count the
        global data size does not divide."""
        pc = self.process_count
        leaves, treedef = jax.tree_util.tree_flatten(graph)
        key = (self.mesh, tuple(self.act_rules.items()), treedef, pc,
               tuple(x.shape for x in leaves))
        cached = _SPEC_CACHE.get(key)
        if cached is not None:
            return cached
        ctx = self._ctx()
        out = jax.tree_util.tree_unflatten(treedef, [
            ctx.resolve(_leaf_axes(x), ctx.act_rules,
                        shape=(x.shape[0] * pc,) + tuple(x.shape[1:]))
            for x in leaves])
        _SPEC_CACHE[key] = out
        return out

    def _put_local(self, x, spec):
        """Process-local batch data -> global array.  The leading group
        axis is the only process-spanning dim of a super-batch leaf, so
        the global shape is the local one scaled by process_count there
        (feature/model dims stay process-local by `make_mesh`'s
        construction)."""
        sharding = NamedSharding(self.mesh, spec)
        x = np.asarray(x)
        ents = tuple(spec)
        lead = ents[0] if ents else None
        lead = lead if isinstance(lead, (tuple, list)) else (lead,)
        scale = self.process_count \
            if any(a in self.data_axes for a in lead if a) else 1
        global_shape = (x.shape[0] * scale,) + tuple(x.shape[1:]) \
            if x.ndim else x.shape
        return jax.make_array_from_process_local_data(sharding, x,
                                                      global_shape)

    def _ctx(self) -> ShardingContext:
        return ShardingContext(self.mesh, self.param_rules, self.act_rules)

    def model_context(self):
        return model_parallel_trace(self.model_axis, self.model_size)

    def dispatch_context(self):
        """Trace-time kernel-dispatch context for steps traced with GLOBAL
        batch shapes (GSPMD auto-sharding): eligibility and e_block
        budgets divide row counts by the data shards and feature widths
        by the model shards.  shard_map bodies see per-shard shapes
        already and must not use this."""
        from repro.kernels import dispatch
        return dispatch.partitioned(data=self.data_size,
                                    model=self.model_size)

    # -- GraphTensor super-batch specs ---------------------------------------

    def graph_logical_axes(self, graph):
        return graph_logical_axes(graph)

    def graph_specs(self, graph):
        """PartitionSpec per leaf (shard_map in_specs / placement),
        resolved through the rule tables with the divisibility fixup —
        a feature width the model axis does not divide replicates.
        Cached per (mesh, rules, tree structure, leaf shapes)."""
        leaves, treedef = jax.tree_util.tree_flatten(graph)
        key = (self.mesh, tuple(self.act_rules.items()), treedef,
               tuple(x.shape for x in leaves))
        cached = _SPEC_CACHE.get(key)
        if cached is not None:
            return cached
        ctx = self._ctx()
        # per-leaf axes computed directly from the flat leaves (an axes
        # *tree* would grow phantom leaves at empty feature dicts, whose
        # () aux tuples flatten as axes leaves)
        out = jax.tree_util.tree_unflatten(treedef, [
            ctx.resolve(_leaf_axes(x), ctx.act_rules, shape=x.shape)
            for x in leaves])
        _SPEC_CACHE[key] = out
        return out

    def graph_shardings(self, graph):
        """NamedSharding per leaf of a stacked super-batch."""
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.graph_specs(graph),
            is_leaf=lambda s: isinstance(s, P))

    def data_spec(self) -> P:
        """Spec sharding a leading batch/group dim over the data axes."""
        axes = self.data_axes
        return P(axes if len(axes) > 1 else axes[0]) if axes else P()

    def gather_graph(self, graph, specs):
        """Entry all-gather for a shard_map body: leaves placed
        model-sharded on their feature dim come back to full width (the
        model code consumes full-width features; the boundary ops re-split
        per reduction)."""
        if self.model_axis is None:
            return graph

        def g(x, spec):
            ents = tuple(spec)
            if ents and ents[-1] == self.model_axis:
                return jax.lax.all_gather(x, self.model_axis,
                                          axis=x.ndim - 1, tiled=True)
            return x
        return jax.tree_util.tree_map(g, graph, specs)

    # -- placement -----------------------------------------------------------

    def put_super_batch(self, graph, labels):
        """Place a host-side super-batch and its per-group labels with the
        plan's 2-D shardings.  A scalar GraphTensor is promoted to a
        [1, ...] stack so the 1-device path runs the identical program.

        Multi-process meshes treat `graph`/`labels` as THIS PROCESS's
        shard of the global batch (the `GraphBatcher(rank, world)`
        stream): leaves become global `jax.Array`s via
        `make_array_from_process_local_data`, stacking the per-process
        group blocks in process-rank order — exactly the global
        super-batch a single-process `GraphBatcher` would emit."""
        from repro.core.graph_tensor import stack_graphs, stack_size
        if stack_size(graph) is None:
            graph = stack_graphs([graph])
            labels = np.asarray(labels)[None]
        n_groups = stack_size(graph)
        if n_groups % self.local_data_size:
            raise ValueError(
                f"super-batch has {n_groups} component groups, not "
                f"divisible by this process's {self.local_data_size} "
                "data shards")
        if not self.is_multiprocess:
            graph = jax.tree_util.tree_map(jax.device_put, graph,
                                           self.graph_shardings(graph))
            labels = jax.device_put(
                jnp.asarray(labels),
                NamedSharding(self.mesh, self.data_spec()))
            return graph, labels
        specs = self._scaled_graph_specs(graph)
        graph = jax.tree_util.tree_map(self._put_local, graph, specs)
        labels = self._put_local(np.asarray(labels), self.data_spec())
        return graph, labels

    def replicate(self, tree):
        """Place a pytree fully replicated over the (possibly
        multi-process) mesh."""
        return jax.tree_util.tree_map(
            lambda x: self._host_put(x, P()), tree)

    # -- ZeRO-1 optimizer-state layout ---------------------------------------

    def param_logical_axes(self, params):
        """Default ZeRO annotation for un-annotated param trees (the GNN
        runner path): the leading dim is logical "embed" (-> "data", the
        FSDP rule), the rest replicate.  Scalars and leaves whose leading
        dim the data axes do not divide resolve to replicated."""
        return jax.tree_util.tree_map(
            lambda p: (("embed",) + (None,) * (p.ndim - 1)) if p.ndim
            else (), params)

    def _resolve_axes_tree(self, axes_tree, values):
        """Resolve a logical-axes tree (plain tuples at leaves) against
        the param rules, with shapes from `values` for divisibility."""
        ctx = self._ctx()
        flat_axes = jax.tree_util.tree_leaves(axes_tree,
                                              is_leaf=is_axes_leaf)
        flat_vals, treedef = jax.tree_util.tree_flatten(values)
        assert len(flat_axes) == len(flat_vals), \
            (len(flat_axes), len(flat_vals))
        specs = [ctx.resolve(a, ctx.param_rules, shape=v.shape)
                 for a, v in zip(flat_axes, flat_vals)]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def _spec_data_dim(self, spec) -> int:
        """Index of the dim a spec shards over the data axes, or -1."""
        for i, e in enumerate(tuple(spec)):
            ents = e if isinstance(e, (tuple, list)) else (e,)
            if any(a in self.data_axes for a in ents):
                return i
        return -1

    def zero_enabled(self) -> bool:
        """ZeRO-1 slicing needs exactly one data axis to index."""
        return self.data_size > 1 and len(self.data_axes) == 1

    def zero_param_specs(self, params, param_axes=None):
        """Per-leaf P for the ZeRO slice of `params` (and of grads)."""
        axes = param_axes if param_axes is not None \
            else self.param_logical_axes(params)
        return self._resolve_axes_tree(axes, params)

    def zero_dims(self, specs):
        """Per-leaf int dim sharded over data (-1 = replicated)."""
        return jax.tree_util.tree_map(self._spec_data_dim, specs,
                                      is_leaf=lambda s: isinstance(s, P))

    def opt_state_specs(self, optimizer, params, opt_state,
                        param_axes=None):
        """Per-leaf P for the optimizer state, via the optimizer's own
        `state_axes` (m/v mirror params; Adafactor's factored vr/vc drop
        the factored dims) resolved through the param rules."""
        axes = param_axes if param_axes is not None \
            else self.param_logical_axes(params)
        if not self.zero_enabled():
            return jax.tree_util.tree_map(lambda x: P(), opt_state)
        state_axes = optimizer.state_axes(axes)
        return self._resolve_axes_tree(state_axes, opt_state)

    def place_opt_state(self, optimizer, params, opt_state,
                        param_axes=None):
        """Place the optimizer state with its ZeRO-1 shardings (the
        placement `make_train_step`'s in_specs expect).  Works on
        multi-process meshes too: `opt.init` runs identically on every
        process, so each host serves its devices' slices of the full
        state."""
        specs = self.opt_state_specs(optimizer, params, opt_state,
                                     param_axes)
        return jax.tree_util.tree_map(self._host_put, opt_state, specs)

    def opt_state_bytes_per_device(self, opt_state) -> int:
        """Bytes of optimizer state resident on one device (the ZeRO-1
        memory metric gated in results/BENCH_mp_scaling.json)."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(opt_state):
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard)) * leaf.dtype.itemsize
        return total

    def zero_reduce_grads(self, grads, dims):
        """Cross-shard gradient mean, delivered pre-sliced for ZeRO:
        sharded leaves reduce-scatter (psum_scatter) over the data axis —
        each shard receives only its averaged slice, ~half the traffic of
        all-reduce-then-slice, on exactly the big tables ZeRO targets —
        while replicated leaves pmean over every axis.  The model-axis
        pmean reassembles the per-chunk cotangents either way."""
        n = self.data_size
        data_ax = self.data_axes[0]
        all_axes = tuple(self.data_axes) + (
            (self.model_axis,) if self.model_axis else ())

        def f(g, d):
            if d < 0:
                return jax.lax.pmean(g, all_axes)
            if self.model_axis:
                g = jax.lax.pmean(g, self.model_axis)
            return jax.lax.psum_scatter(g, data_ax, scatter_dimension=d,
                                        tiled=True) / n
        return jax.tree_util.tree_map(f, grads, dims)

    def zero_slice(self, tree, dims):
        """This data shard's slice of each leaf (identity for dim -1)."""
        n = self.data_size
        ax = self.data_axes[0]

        def f(x, d):
            if d < 0:
                return x
            w = x.shape[d] // n
            i = jax.lax.axis_index(ax)
            return jax.lax.dynamic_slice_in_dim(x, i * w, w, axis=d)
        return jax.tree_util.tree_map(f, tree, dims)

    def zero_gather(self, tree, dims):
        """All-gather updated parameter slices back to full leaves."""
        ax = self.data_axes[0]

        def f(x, d):
            if d < 0:
                return x
            return jax.lax.all_gather(x, ax, axis=d, tiled=True)
        return jax.tree_util.tree_map(f, tree, dims)


def make_plan(num_devices: Optional[int] = None, *, model_parallel: int = 1,
              param_rules: Mapping[str, Any] | None = None,
              act_rules: Mapping[str, Any] | None = None) -> MeshPlan:
    """Build the mesh and its MeshPlan in one call (the runner entry)."""
    return plan_for(make_mesh(num_devices, model_parallel=model_parallel),
                    param_rules=param_rules, act_rules=act_rules)


def plan_for(mesh: Mesh, *, param_rules=None, act_rules=None) -> MeshPlan:
    """Wrap an existing mesh (e.g. from `graph_sharding.make_data_mesh`
    or `launch.mesh.make_host_mesh`) in a MeshPlan."""
    return MeshPlan(mesh,
                    dict(DEFAULT_PARAM_RULES, **(param_rules or {})),
                    dict(DEFAULT_ACT_RULES, **(act_rules or {})))


# ---------------------------------------------------------------------------
# Train / eval steps
# ---------------------------------------------------------------------------

def _local_mean(loss_fn, params, graph_stack, labels):
    """Mean loss over this shard's local component groups (a static Python
    loop — the local group count is known at trace time)."""
    from repro.core.graph_tensor import unstack_graph
    groups = unstack_graph(graph_stack)
    total = 0.0
    for i, g in enumerate(groups):
        total = total + loss_fn(params, g, labels[i])
    return total / len(groups)


def _pmean(tree, axis):
    return jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis), tree)


def make_train_step(plan: MeshPlan, loss_fn: Callable, optimizer, *,
                    num_groups: int, zero1: bool = True) -> Callable:
    """The 2-D training step.

    loss_fn(params, scalar_graph, group_labels) -> scalar loss.  Returns a
    jit'd ``(params, opt_state, graph_stack, labels) -> (params, opt_state,
    loss)``: graph_stack is a [num_groups, ...] super-batch placed with
    ``plan.put_super_batch`` (groups over "data", feature dims over
    "model"), opt_state placed with ``plan.place_opt_state``.  Per-shard
    forward/backward with the ops-level model-parallel gather boundaries,
    gradient pmean over all mesh axes, ZeRO-1 optimizer update (each data
    shard updates its parameter slice, psum-corrected clipping, params
    all-gathered), donated state.
    """
    mesh = plan.mesh
    dp = plan.data_size
    if num_groups % dp:
        raise ValueError(f"num_groups {num_groups} not divisible by "
                         f"{dp} data shards")
    all_axes = tuple(plan.data_axes) + (
        (plan.model_axis,) if plan.model_axis else ())
    zero = zero1 and plan.zero_enabled()

    def train_step(params, opt_state, graph_stack, labels):
        # specs are derived from trace-time shapes, so the shard_map is
        # constructed here (and cached with the jit trace)
        gspecs = plan.graph_specs(graph_stack)
        pspecs = plan.zero_param_specs(params) if zero else None
        pdims = plan.zero_dims(pspecs) if zero else None
        sspecs = plan.opt_state_specs(optimizer, params, opt_state) \
            if zero else jax.tree_util.tree_map(lambda x: P(), opt_state)

        def body(params, opt_local, graph_stack, labels):
            graph_stack = plan.gather_graph(graph_stack, gspecs)
            with plan.model_context():
                loss, grads = jax.value_and_grad(
                    lambda p: _local_mean(loss_fn, p, graph_stack,
                                          labels))(params)
            # over "data": the cross-replica grad reduction; over
            # "model": reassembles the disjoint per-chunk cotangents the
            # feature-split boundaries produce (exact).
            loss = jax.lax.pmean(loss, all_axes)
            if zero:
                # sharded leaves arrive pre-sliced via reduce-scatter
                g_loc = plan.zero_reduce_grads(grads, pdims)
                p_loc = plan.zero_slice(params, pdims)
                p_new, opt_local, _ = optimizer.update(
                    g_loc, opt_local, p_loc, axis_name=plan.data_axes,
                    shard_dims=pdims)
                params = plan.zero_gather(p_new, pdims)
            else:
                grads = _pmean(grads, all_axes)
                params, opt_local, _ = optimizer.update(grads, opt_local,
                                                        params)
            return params, opt_local, loss

        # no replication checker: the replicated outputs are pmean/psum/
        # all_gather results, so its proof adds tracing cost, not value
        sharded = shard_map(
            body, mesh=mesh,
            in_specs=(P(), sspecs, gspecs, plan.data_spec()),
            out_specs=(P(), sspecs, P()), check_vma=False)
        return sharded(params, opt_state, graph_stack, labels)

    # donate params/opt_state: the returned trees reuse the input buffers,
    # which matters on replicated params (every leaf otherwise reallocates
    # on every device every step)
    return jax.jit(train_step, donate_argnums=(0, 1))


def make_eval_step(plan: MeshPlan, metric_fn: Callable) -> Callable:
    """The 2-D eval step.  metric_fn(params, scalar_graph, group_labels)
    -> tuple of scalars; each is summed over groups and psum'd across
    data shards (counts, not means — divide at the caller)."""
    mesh = plan.mesh

    def eval_step(params, graph_stack, labels):
        from repro.core.graph_tensor import stack_size, unstack_graph
        if stack_size(graph_stack) % plan.data_size:
            raise ValueError(
                f"eval super-batch has {stack_size(graph_stack)} groups, "
                f"not divisible by {plan.data_size} data shards")
        gspecs = plan.graph_specs(graph_stack)

        def body(params, graph_stack, labels):
            graph_stack = plan.gather_graph(graph_stack, gspecs)
            with plan.model_context():
                totals = None
                for i, g in enumerate(unstack_graph(graph_stack)):
                    out = metric_fn(params, g, labels[i])
                    totals = out if totals is None else tuple(
                        a + b for a, b in zip(totals, out))
            return tuple(jax.lax.psum(t, plan.data_axes) for t in totals)

        sharded = shard_map(
            body, mesh=mesh,
            in_specs=(P(), gspecs, plan.data_spec()),
            out_specs=P(), check_vma=False)
        return sharded(params, graph_stack, labels)

    return jax.jit(eval_step)
