"""GraphUpdate (paper §4.2.2, Eq. 1–3): one round of heterogeneous message
passing assembled from per-edge-set Convs and per-node-set NextState maps,
plus optional edge-set and context updates (full Graph Networks)."""
from __future__ import annotations

import math
from typing import Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core.graph_tensor import (CONTEXT, GraphTensor, HIDDEN_STATE,
                                     SOURCE, TARGET)
from repro.nn.layers import (ACTIVATIONS, Linear, LayerNorm,
                             rational_sigmoid)
from repro.nn.module import Module, Param


class NextStateFromConcat(Module):
    """next_state = fn(concat(old state, all inputs)) (paper Fig. 7)."""

    def __init__(self, in_dim: int, units: int, *, activation: str = "relu",
                 use_layer_norm: bool = False):
        self.dense = Linear(in_dim, units)
        self.act = ACTIVATIONS[activation]
        self.norm = LayerNorm(units) if use_layer_norm else None

    def init(self, key):
        k1, k2 = jax.random.split(key)
        p = {"dense": self.dense.init(k1)}
        if self.norm is not None:
            p["norm"] = self.norm.init(k2)
        return p

    def __call__(self, params, old_state, inputs: list):
        x = jnp.concatenate([old_state] + list(inputs), axis=-1)
        y = self.act(self.dense(params["dense"], x))
        if self.norm is not None:
            y = self.norm(params["norm"], y)
        return y


class ResidualNextState(Module):
    """next_state = old + fn(concat(...)); used by deeper GNN stacks."""

    def __init__(self, in_dim: int, units: int, *, activation: str = "relu"):
        self.inner = NextStateFromConcat(in_dim, units, activation=activation)

    def init(self, key):
        return {"inner": self.inner.init(key)}

    def __call__(self, params, old_state, inputs: list):
        return old_state + self.inner(params["inner"], old_state, inputs)


class SingleInputNextState(Module):
    """Passes through the single pooled message (paper GCN Eq. 4)."""

    def init(self, key):
        return {}

    def __call__(self, params, old_state, inputs: list):
        assert len(inputs) == 1
        return inputs[0]


class NodeSetUpdate(Module):
    """{edge_set_name: Conv} + NextState for one node set (paper Eq. 1).

    Convs that expose a fused kernel path (e.g. SimpleConv's `edge_mpnn`
    route via repro.kernels.dispatch) use it transparently — each conv is
    invoked with the full graph, so a whole message-passing round runs
    fused when every conv in the round is dispatch-eligible; see
    `describe_dispatch` for which path each conv takes and why.
    """

    def __init__(self, convs: Mapping[str, Module], next_state: Module):
        self.convs = dict(sorted(convs.items()))
        self.next_state = next_state

    def describe_dispatch(self, params, graph: GraphTensor) -> dict:
        """{edge_set_name: dispatch Decision (or None for convs that do
        not report one)}: the fused kernel's, or else the pool's."""
        return {name: (conv.describe_dispatch(params["convs"][name], graph,
                                              name)
                       if hasattr(conv, "describe_dispatch") else None)
                for name, conv in self.convs.items()}

    def init(self, key):
        keys = jax.random.split(key, len(self.convs) + 1)
        return {
            "convs": {name: conv.init(k)
                      for (name, conv), k in zip(self.convs.items(), keys)},
            "next_state": self.next_state.init(keys[-1]),
        }

    def __call__(self, params, graph: GraphTensor, node_set_name: str):
        old = graph.node_sets[node_set_name][HIDDEN_STATE]
        pooled = [conv(params["convs"][name], graph, name)
                  for name, conv in self.convs.items()]
        return self.next_state(params["next_state"], old, pooled)


class EdgeSetUpdate(Module):
    """Materialised per-edge state update (paper Eq. 3, NextEdgeState)."""

    def __init__(self, in_dim: int, units: int, *, activation: str = "relu",
                 use_receiver_state: bool = True,
                 use_sender_state: bool = True):
        self.next_state = NextStateFromConcat(in_dim, units,
                                              activation=activation)
        self.use_receiver_state = use_receiver_state
        self.use_sender_state = use_sender_state

    def init(self, key):
        return {"next_state": self.next_state.init(key)}

    def __call__(self, params, graph: GraphTensor, edge_set_name: str):
        es = graph.edge_sets[edge_set_name]
        inputs = []
        if self.use_sender_state:
            inputs.append(ops.broadcast_node_to_edges(
                graph, edge_set_name, SOURCE, feature_name=HIDDEN_STATE))
        if self.use_receiver_state:
            inputs.append(ops.broadcast_node_to_edges(
                graph, edge_set_name, TARGET, feature_name=HIDDEN_STATE))
        old = es.features.get(HIDDEN_STATE)
        if old is None:
            old = inputs[0]
            inputs = inputs[1:]
        return self.next_state(params["next_state"], old, inputs)


class ContextUpdate(Module):
    """Pool node states per component and update the context state."""

    def __init__(self, node_set_names: list[str], in_dim: int, units: int,
                 *, reduce_type: str = "mean", activation: str = "relu"):
        self.node_set_names = list(node_set_names)
        self.reduce_type = reduce_type
        self.next_state = NextStateFromConcat(in_dim, units,
                                              activation=activation)

    def init(self, key):
        return {"next_state": self.next_state.init(key)}

    def __call__(self, params, graph: GraphTensor):
        pooled = [ops.pool_nodes_to_context(graph, name, self.reduce_type,
                                            feature_name=HIDDEN_STATE)
                  for name in self.node_set_names]
        old = graph.context.features.get(HIDDEN_STATE)
        if old is None:
            old = pooled[0]
            pooled = pooled[1:]
        return self.next_state(params["next_state"], old, pooled)


class GraphUpdate(Module):
    """One message-passing round over the whole heterogeneous graph.

    Applies (in order): edge-set updates, node-set updates, context update —
    the Graph Networks schedule generalised to named sets.  Each returns a
    new GraphTensor with replaced hidden states; an edge- or node-set
    update runs under a `jax.named_scope` named for its set.

    With kernels enabled (repro.core.ops.use_kernels / REPRO_KERNELS) the
    hot path of a round — gather, per-edge message, scatter-pool — runs
    through the Pallas kernels behind repro.kernels.dispatch;
    `describe_dispatch` reports the per-conv routing decisions.
    """

    def __init__(self, *,
                 node_sets: Mapping[str, NodeSetUpdate] | None = None,
                 edge_sets: Mapping[str, EdgeSetUpdate] | None = None,
                 context: ContextUpdate | None = None):
        self.node_sets = dict(sorted((node_sets or {}).items()))
        self.edge_sets = dict(sorted((edge_sets or {}).items()))
        self.context = context

    def init(self, key):
        n = len(self.node_sets) + len(self.edge_sets) + 1
        keys = jax.random.split(key, n)
        i = 0
        p = {"node_sets": {}, "edge_sets": {}}
        for name, upd in self.edge_sets.items():
            p["edge_sets"][name] = upd.init(keys[i])
            i += 1
        for name, upd in self.node_sets.items():
            p["node_sets"][name] = upd.init(keys[i])
            i += 1
        if self.context is not None:
            p["context"] = self.context.init(keys[i])
        return p

    def describe_dispatch(self, params, graph: GraphTensor) -> dict:
        """{node_set_name: {edge_set_name: dispatch Decision | None}} —
        which kernel path (fused conv or pool) each conv of this round
        would take on `graph`, and why."""
        return {name: upd.describe_dispatch(params["node_sets"][name],
                                            graph)
                for name, upd in self.node_sets.items()
                if hasattr(upd, "describe_dispatch")}

    def __call__(self, params, graph: GraphTensor) -> GraphTensor:
        if self.edge_sets:
            new_edge_feats = {}
            for name, upd in self.edge_sets.items():
                feats = dict(graph.edge_sets[name].features)
                with jax.named_scope(name):
                    feats[HIDDEN_STATE] = upd(params["edge_sets"][name],
                                              graph, name)
                new_edge_feats[name] = feats
            graph = graph.replace_features(edge_sets=new_edge_feats)
        if self.node_sets:
            new_node_feats = {}
            for name, upd in self.node_sets.items():
                feats = dict(graph.node_sets[name].features)
                with jax.named_scope(name):
                    feats[HIDDEN_STATE] = upd(params["node_sets"][name],
                                              graph, name)
                new_node_feats[name] = feats
            graph = graph.replace_features(node_sets=new_node_feats)
        if self.context is not None:
            feats = dict(graph.context.features)
            feats[HIDDEN_STATE] = self.context(params["context"], graph)
            graph = graph.replace_features(context=feats)
        return graph


class HGTUpdate(Module):
    """One round of the Heterogeneous Graph Transformer (Hu, Dong, Wang,
    Sun, WWW 2020, Eq. 1-5), over every node set that receives.

    For an edge e = (s, t) of relation r, with t its receiving end by
    `receiver_tag` and head i of width d:

        ATT_i(e) = (K_i(s) W_att[r, i] . Q_i(t)) mu[r, i] / sqrt(d)
        H^(t)    = concat_i sum_e softmax_i(e) V_i(s) W_msg[r, i]

    where the softmax runs over every edge into t in all of its relations
    at once (`ops.segment_softmax` over a list of edge sets), and

        H'(t) = LayerNorm(sigmoid(alpha) A(GELU(H^(t)))
                          + (1 - sigmoid(alpha)) H(t)).

    K, V (sending sets), Q, A, alpha and the norm (receiving sets) are
    keyed by node set, so every relation of a node set shares them;
    W_att, W_msg and the prior mu are keyed by edge set.  The relation's
    matrices run at node level: ``K W_att . Q = K . (W_att Q)`` at the
    receiver, and ``sum_e c_e V_e W_msg = (sum_e c_e V_e) W_msg``, so no
    per-edge matmul is needed.  A node set that receives over no edge
    set keeps its state.  Each set's work runs under a
    `jax.named_scope` of its name; the relation transforms and gathers
    under ``hgt_relation``, the scores, joint softmax and weighted pool
    under ``hgt_attention``.
    """

    def __init__(self, edges: Mapping[str, tuple[str, str]], dim: int, *,
                 num_heads: int, receiver_tag: str = TARGET,
                 use_layer_norm: bool = True):
        if dim % num_heads:
            raise ValueError(f"dim {dim} is not a multiple of num_heads "
                             f"{num_heads}")
        if receiver_tag not in (SOURCE, TARGET):
            raise ValueError(f"receiver_tag must be SOURCE or TARGET, got "
                             f"{receiver_tag!r}")
        self.dim, self.num_heads = dim, num_heads
        self.per_head = dim // num_heads
        self.tag = receiver_tag
        self.sender_tag = SOURCE if receiver_tag == TARGET else TARGET
        # edge set -> (sending node set, receiving node set)
        self.ends = {es: (src, tgt) if receiver_tag == TARGET else (tgt, src)
                     for es, (src, tgt) in sorted(edges.items())}
        self.incoming: dict = {}
        for es, (_, recv) in self.ends.items():
            self.incoming.setdefault(recv, []).append(es)
        self.incoming = dict(sorted(self.incoming.items()))
        self.senders = sorted({s for s, _ in self.ends.values()})
        self.node_types = sorted(set(self.senders) | set(self.incoming))
        self.proj = Linear(dim, dim)
        self.norm = LayerNorm(dim) if use_layer_norm else None

    def init(self, key):
        p = {"node_sets": {}, "edge_sets": {}}
        for ns in self.node_types:
            key, *ks = jax.random.split(key, 5)
            t = {}
            if ns in self.senders:
                t["k"], t["v"] = self.proj.init(ks[0]), self.proj.init(ks[1])
            if ns in self.incoming:
                t["q"], t["a"] = self.proj.init(ks[2]), self.proj.init(ks[3])
                t["skip"] = {"scale": Param(jnp.ones((1,)), (None,))}
                if self.norm is not None:
                    t["norm"] = self.norm.init(None)
            p["node_sets"][ns] = t
        d, h = self.per_head, self.num_heads
        for es in self.ends:
            key, k1, k2 = jax.random.split(key, 3)
            p["edge_sets"][es] = {
                "att": {"w": Param(jax.random.normal(k1, (d, h, d))
                                   / math.sqrt(d), (None, None, None))},
                "msg": {"w": Param(jax.random.normal(k2, (d, h, d))
                                   / math.sqrt(d), (None, None, None))},
                "prior": {"scale": Param(jnp.ones((h,)), (None,))}}
        return p

    def _heads(self, x):
        return x.reshape(x.shape[0], self.num_heads, self.per_head)

    def __call__(self, params, graph: GraphTensor) -> GraphTensor:
        keys, values, queries = {}, {}, {}
        for ns in self.node_types:
            p = params["node_sets"][ns]
            h = graph.node_sets[ns][HIDDEN_STATE]
            with jax.named_scope(ns):
                if ns in self.senders:
                    keys[ns] = self._heads(self.proj(p["k"], h))
                    values[ns] = self._heads(self.proj(p["v"], h))
                if ns in self.incoming:
                    queries[ns] = self._heads(self.proj(p["q"], h))
        new_feats = {}
        for ns, incoming in self.incoming.items():
            with jax.named_scope(ns):
                feats = dict(graph.node_sets[ns].features)
                feats[HIDDEN_STATE] = self._receive(
                    params, graph, ns, incoming, keys, values, queries[ns])
                new_feats[ns] = feats
        return graph.replace_features(node_sets=new_feats)

    def _receive(self, params, graph, ns, incoming, keys, values, query):
        scores, sent = [], []
        for es in incoming:
            sender = self.ends[es][0]
            r = params["edge_sets"][es]
            with jax.named_scope("hgt_relation"):
                q_att = jnp.einsum("nhf,dhf->nhd", query,
                                   r["att"]["w"].astype(query.dtype))
                k = ops.broadcast_node_to_edges(
                    graph, es, self.sender_tag, feature_value=keys[sender])
                q = ops.broadcast_node_to_edges(graph, es, self.tag,
                                                feature_value=q_att)
                sent.append(ops.broadcast_node_to_edges(
                    graph, es, self.sender_tag,
                    feature_value=values[sender]))
            with jax.named_scope("hgt_attention"):
                scores.append(jnp.sum(k * q, axis=-1) * r["prior"]["scale"]
                              / math.sqrt(self.per_head))
        with jax.named_scope("hgt_attention"):
            coefs = ops.segment_softmax(graph, incoming, self.tag,
                                        feature_value=scores)
            pooled = [ops.pool_edges_to_node(graph, es, self.tag, "sum",
                                             feature_value=v * c[..., None])
                      for es, v, c in zip(incoming, sent, coefs)]
        with jax.named_scope("hgt_relation"):
            msg = sum(jnp.einsum("nhd,dhf->nhf", x, params["edge_sets"][es]
                                 ["msg"]["w"].astype(x.dtype))
                      for es, x in zip(incoming, pooled))
        p = params["node_sets"][ns]
        old = graph.node_sets[ns][HIDDEN_STATE]
        update = self.proj(p["a"], jax.nn.gelu(
            msg.reshape(msg.shape[0], self.dim), approximate=False))
        alpha = rational_sigmoid(p["skip"]["scale"])
        out = alpha * update + (1 - alpha) * old
        return self.norm(p["norm"], out) if self.norm is not None else out


class MapFeatures(Module):
    """Per-set feature transformations (paper §4.2.1).

    fns: {"node_sets": {name: callable(params, feats)->feats}, ...} where
    each callable is a Module; used to build initial hidden states.
    """

    def __init__(self, node_sets: Mapping[str, Module] | None = None,
                 edge_sets: Mapping[str, Module] | None = None,
                 context: Module | None = None):
        self.node_sets = dict(sorted((node_sets or {}).items()))
        self.edge_sets = dict(sorted((edge_sets or {}).items()))
        self.context = context

    def init(self, key):
        n = len(self.node_sets) + len(self.edge_sets) + 1
        keys = jax.random.split(key, n)
        i = 0
        p = {"node_sets": {}, "edge_sets": {}}
        for name, fn in self.node_sets.items():
            p["node_sets"][name] = fn.init(keys[i])
            i += 1
        for name, fn in self.edge_sets.items():
            p["edge_sets"][name] = fn.init(keys[i])
            i += 1
        if self.context is not None:
            p["context"] = self.context.init(keys[i])
        return p

    def __call__(self, params, graph: GraphTensor) -> GraphTensor:
        node_feats = {
            name: fn(params["node_sets"][name],
                     graph.node_sets[name].features)
            for name, fn in self.node_sets.items()}
        edge_feats = {
            name: fn(params["edge_sets"][name],
                     graph.edge_sets[name].features)
            for name, fn in self.edge_sets.items()}
        ctx = (self.context(params["context"], graph.context.features)
               if self.context is not None else None)
        return graph.replace_features(
            context=ctx,
            node_sets=node_feats or None,
            edge_sets=edge_feats or None)
