"""Bundled GNN model collection (paper §4.3 / §8 and Table 1 baselines).

Each model factory takes the *graph structure* (node sets, edge sets with
their endpoints) plus widths, and returns a Module whose __call__ maps a
GraphTensor (with "hidden_state" features) to an updated GraphTensor after
`num_rounds` of message passing.  These are the concrete instantiations of
GraphUpdate used by the OGBN-MAG case study and the benchmarks.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.core.convolutions import (GATv2Conv, GCNConv,
                                     MultiHeadAttentionConv, SAGEConv,
                                     SimpleConv)
from repro.core.graph_tensor import (GraphTensor, HIDDEN_STATE, SOURCE,
                                     TARGET)
from repro.core.graph_update import (GraphUpdate, HGTUpdate, MapFeatures,
                                     NextStateFromConcat, NodeSetUpdate,
                                     SingleInputNextState)
from repro.core.schema import GraphSchema
from repro.nn.layers import Linear, rational_tanh
from repro.nn.module import Module


def incident_edge_sets(schema_edges: Mapping[str, tuple[str, str]],
                       node_set: str) -> list[str]:
    """Edge sets whose TARGET is `node_set` (the receiving convention)."""
    return [name for name, (src, tgt) in schema_edges.items()
            if tgt == node_set]


class GNNStack(Module):
    """A sequence of GraphUpdate rounds (optionally weight-shared), each
    under a `jax.named_scope` ``round_<i>``."""

    def __init__(self, updates: Sequence[GraphUpdate], *,
                 share_weights: bool = False):
        self.updates = list(updates)
        self.share_weights = share_weights

    def init(self, key):
        if self.share_weights:
            return {"rounds": [self.updates[0].init(key)] * len(self.updates)}
        keys = jax.random.split(key, len(self.updates))
        return {"rounds": [u.init(k) for u, k in zip(self.updates, keys)]}

    def __call__(self, params, graph: GraphTensor) -> GraphTensor:
        for i, (upd, p) in enumerate(zip(self.updates, params["rounds"])):
            with jax.named_scope(f"round_{i}"):
                graph = upd(p, graph)
        return graph


def vanilla_mpnn(edges: Mapping[str, tuple[str, str]],
                 node_dims: Mapping[str, int], *,
                 message_dim: int = 128, hidden_dim: int = 128,
                 num_rounds: int = 4, reduce_type: str = "sum",
                 receiver_tag: str = TARGET,
                 use_layer_norm: bool = True,
                 skip_node_sets: Sequence[str] = ()) -> GNNStack:
    """The paper's §8 VanillaMPNN: per-edge-set SimpleConv + per-node-set
    NextStateFromConcat (Fig. 7/8), generalised over an arbitrary schema."""
    updates = []
    for rnd in range(num_rounds):
        node_updates = {}
        for ns, dim in node_dims.items():
            if ns in skip_node_sets:
                continue
            convs = {}
            for es, (src, tgt) in edges.items():
                if (tgt if receiver_tag == TARGET else src) != ns:
                    continue
                sender = src if receiver_tag == TARGET else tgt
                in_dim = node_dims[sender] + dim if rnd == 0 else \
                    hidden_dim * 2
                # after round 0 all states are hidden_dim wide
                sender_dim = node_dims[sender] if rnd == 0 else hidden_dim
                recv_dim = dim if rnd == 0 else hidden_dim
                convs[es] = SimpleConv(message_dim, sender_dim + recv_dim,
                                       reduce_type=reduce_type,
                                       receiver_tag=receiver_tag)
            if not convs:
                continue
            recv_dim = dim if rnd == 0 else hidden_dim
            next_in = recv_dim + message_dim * len(convs)
            node_updates[ns] = NodeSetUpdate(
                convs, NextStateFromConcat(next_in, hidden_dim,
                                           use_layer_norm=use_layer_norm))
        updates.append(GraphUpdate(node_sets=node_updates))
    return GNNStack(updates)


def rgcn(edges: Mapping[str, tuple[str, str]],
         node_dims: Mapping[str, int], *, hidden_dim: int = 128,
         num_rounds: int = 2) -> GNNStack:
    """R-GCN (paper Eq. 5): per-edge-set mean-pooled linear messages plus a
    self-transform, summed."""

    class RGCNNextState(Module):
        def __init__(self, in_dim):
            self.w_self = Linear(in_dim, hidden_dim, use_bias=False)

        def init(self, key):
            return {"w_self": self.w_self.init(key)}

        def __call__(self, params, old, inputs):
            return jax.nn.relu(
                sum(inputs) + self.w_self(params["w_self"], old))

    updates = []
    for rnd in range(num_rounds):
        node_updates = {}
        for ns, dim in node_dims.items():
            convs = {}
            for es, (src, tgt) in edges.items():
                if tgt != ns:
                    continue
                sender_dim = node_dims[src] if rnd == 0 else hidden_dim
                convs[es] = SAGEConv(hidden_dim, sender_dim,
                                     aggregator="mean")
            if not convs:
                continue
            recv_dim = dim if rnd == 0 else hidden_dim
            node_updates[ns] = NodeSetUpdate(convs, RGCNNextState(recv_dim))
        updates.append(GraphUpdate(node_sets=node_updates))
    return GNNStack(updates)


def gcn(edge_set: str, node_set: str, in_dim: int, *,
        hidden_dim: int = 64, num_rounds: int = 2) -> GNNStack:
    """Homogeneous GCN (paper Eq. 4) — expects self-loops in the data."""
    updates = []
    for rnd in range(num_rounds):
        conv = GCNConv(hidden_dim, in_dim if rnd == 0 else hidden_dim)
        updates.append(GraphUpdate(node_sets={
            node_set: NodeSetUpdate({edge_set: conv},
                                    SingleInputNextState())}))
    return GNNStack(updates)


def graph_sage(edges: Mapping[str, tuple[str, str]],
               node_dims: Mapping[str, int], *, hidden_dim: int = 128,
               num_rounds: int = 2, aggregator: str = "mean") -> GNNStack:
    updates = []
    for rnd in range(num_rounds):
        node_updates = {}
        for ns, dim in node_dims.items():
            convs = {}
            for es, (src, tgt) in edges.items():
                if tgt != ns:
                    continue
                sender_dim = node_dims[src] if rnd == 0 else hidden_dim
                convs[es] = SAGEConv(hidden_dim, sender_dim,
                                     aggregator=aggregator)
            if not convs:
                continue
            recv_dim = dim if rnd == 0 else hidden_dim
            node_updates[ns] = NodeSetUpdate(
                convs, NextStateFromConcat(
                    recv_dim + hidden_dim * len(convs), hidden_dim))
        updates.append(GraphUpdate(node_sets=node_updates))
    return GNNStack(updates)


def gatv2(edges: Mapping[str, tuple[str, str]],
          node_dims: Mapping[str, int], *, num_heads: int = 4,
          per_head: int = 32, num_rounds: int = 2) -> GNNStack:
    """Heterogeneous GATv2 (paper §4.3: the GAT→R-GCN-style generalisation:
    attention within each edge set, relation importance via separate
    weights)."""
    hidden = num_heads * per_head
    updates = []
    for rnd in range(num_rounds):
        node_updates = {}
        for ns, dim in node_dims.items():
            convs = {}
            for es, (src, tgt) in edges.items():
                if tgt != ns:
                    continue
                in_dim = node_dims[src] if rnd == 0 else hidden
                # GATv2Conv queries use receiver dim; align by projecting
                convs[es] = GATv2Conv(num_heads, per_head,
                                      dim if rnd == 0 else hidden)
            if not convs:
                continue
            recv_dim = dim if rnd == 0 else hidden
            node_updates[ns] = NodeSetUpdate(
                convs, NextStateFromConcat(
                    recv_dim + hidden * len(convs), hidden))
        updates.append(GraphUpdate(node_sets=node_updates))
    return GNNStack(updates)


def hgt_like(edges: Mapping[str, tuple[str, str]],
             node_dims: Mapping[str, int], *, num_heads: int = 4,
             per_head: int = 32, num_rounds: int = 2) -> GNNStack:
    """Heterogeneous transformer-conv stack (the paper's Table-1 competitor
    family: per-edge-set dot-product attention with a softmax and weights
    per edge set, concatenated into the next state).  Not HGT, whose
    softmax and weights span relations: see `hgt`."""
    hidden = num_heads * per_head
    updates = []
    for rnd in range(num_rounds):
        node_updates = {}
        for ns, dim in node_dims.items():
            convs = {}
            for es, (src, tgt) in edges.items():
                if tgt != ns:
                    continue
                convs[es] = MultiHeadAttentionConv(
                    num_heads, per_head, dim if rnd == 0 else hidden)
            if not convs:
                continue
            recv_dim = dim if rnd == 0 else hidden
            node_updates[ns] = NodeSetUpdate(
                convs, NextStateFromConcat(
                    recv_dim + hidden * len(convs), hidden))
        updates.append(GraphUpdate(node_sets=node_updates))
    return GNNStack(updates)


class _TanhLinear(Module):
    """hidden_state -> tanh(Linear(hidden_state)), with `rational_tanh`."""

    def __init__(self, in_dim: int, units: int):
        self.dense = Linear(in_dim, units)

    def init(self, key):
        return self.dense.init(key)

    def __call__(self, params, feats):
        return dict(feats, **{HIDDEN_STATE: rational_tanh(
            self.dense(params, feats[HIDDEN_STATE]))})


def hgt(edges: Mapping[str, tuple[str, str]],
        node_dims: Mapping[str, int], *, num_heads: int = 8,
        per_head: int = 32, num_rounds: int = 3,
        receiver_tag: str = TARGET,
        use_layer_norm: bool = True) -> GNNStack:
    """The Heterogeneous Graph Transformer (Hu et al., WWW 2020).  The
    stack's first entry (``round_0``) takes each node set's states
    through tanh(Linear) to num_heads x per_head, as HGT's reference code
    adapts its inputs; then come `num_rounds` `HGTUpdate`s, each with one
    softmax per receiver over all of its relations.  No dropout and no
    relative temporal encoding."""
    dim = num_heads * per_head
    inputs = MapFeatures(node_sets={ns: _TanhLinear(d, dim)
                                    for ns, d in node_dims.items()})
    return GNNStack([inputs] + [
        HGTUpdate(edges, dim, num_heads=num_heads,
                  receiver_tag=receiver_tag, use_layer_norm=use_layer_norm)
        for _ in range(num_rounds)])
