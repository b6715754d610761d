"""Graph convolutions (paper §4.2.2 Eq. 2 / §4.3 / Appendix A.4).

`AnyToAnyConv` is the unified base of the paper's Appendix A.4: a Conv
computes messages from senders (nodes and/or edge features) and pools them
at a receiver, where the receiver may be the edge set's SOURCE or TARGET
node set, or the CONTEXT.  GATv2Conv subclasses it exactly as in the paper.

All convs take (params, graph, edge_set_name[, receiver_tag]) and return
the pooled message tensor shaped like a feature of the receiver set.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core.graph_tensor import (CONTEXT, GraphTensor, HIDDEN_STATE,
                                     SOURCE, TARGET)
from repro.kernels import dispatch as kernel_dispatch
from repro.nn.layers import Linear, ACTIVATIONS
from repro.nn.module import Module, Param

_OTHER = {SOURCE: TARGET, TARGET: SOURCE}


class AnyToAnyConv(Module):
    """Base class handling the broadcast/pool plumbing for all receiver
    kinds; subclasses implement `convolve`."""

    def __init__(self, *, receiver_tag: str = TARGET,
                 receiver_feature: str | None = HIDDEN_STATE,
                 sender_node_feature: str | None = HIDDEN_STATE,
                 sender_edge_feature: str | None = None):
        self.receiver_tag = receiver_tag
        self.receiver_feature = receiver_feature
        self.sender_node_feature = sender_node_feature
        self.sender_edge_feature = sender_edge_feature

    @property
    def takes_sender_node_input(self) -> bool:
        return self.sender_node_feature is not None

    @property
    def takes_sender_edge_input(self) -> bool:
        return self.sender_edge_feature is not None

    def __call__(self, params, graph: GraphTensor, edge_set_name: str):
        tag = self.receiver_tag
        es = graph.edge_sets[edge_set_name]
        if tag == CONTEXT:
            # receivers are graph components; senders are the edges' items
            def broadcast_from_receiver(value):
                return ops.broadcast_context_to_edges(graph, edge_set_name,
                                                      feature_value=value)

            def pool_to_receiver(value, reduce_type="sum"):
                return ops.pool_edges_to_context(graph, edge_set_name,
                                                 reduce_type,
                                                 feature_value=value)

            def extra_softmax(value):
                comp = es.component_ids()
                # reuse segment softmax over components
                c = graph.num_components
                mask = es.mask()
                mb = mask.reshape(mask.shape + (1,) * (value.ndim - 1))
                scores = jnp.where(mb, value, -jnp.inf)
                m = jax.ops.segment_max(scores, comp, num_segments=c)
                m = jnp.where(jnp.isfinite(m), m, 0)
                e = jnp.where(mb, jnp.exp(scores - jnp.take(m, comp, 0)), 0)
                z = jax.ops.segment_sum(e, comp, num_segments=c)
                return e / jnp.maximum(jnp.take(z, comp, 0), 1e-37)

            receiver_input = (graph.context[self.receiver_feature]
                              if self.receiver_feature else None)
            sender_node_input = None
            if self.takes_sender_node_input:
                sender_node_input = ops.broadcast_node_to_edges(
                    graph, edge_set_name, SOURCE,
                    feature_name=self.sender_node_feature)
        else:
            sender_tag = _OTHER[tag]

            def broadcast_from_receiver(value):
                return ops.broadcast_node_to_edges(graph, edge_set_name, tag,
                                                   feature_value=value)

            def pool_to_receiver(value, reduce_type="sum"):
                return ops.pool_edges_to_node(graph, edge_set_name, tag,
                                              reduce_type,
                                              feature_value=value)

            def extra_softmax(value):
                return ops.segment_softmax(graph, edge_set_name, tag,
                                           feature_value=value)

            receiver_name = (es.adjacency.target_name if tag == TARGET
                             else es.adjacency.source_name)
            receiver_input = (
                graph.node_sets[receiver_name][self.receiver_feature]
                if self.receiver_feature else None)
            sender_node_input = None
            if self.takes_sender_node_input:
                sender_node_input = ops.broadcast_node_to_edges(
                    graph, edge_set_name, sender_tag,
                    feature_name=self.sender_node_feature)
        sender_edge_input = (es[self.sender_edge_feature]
                             if self.takes_sender_edge_input else None)
        return self.convolve(
            params,
            sender_node_input=sender_node_input,
            sender_edge_input=sender_edge_input,
            receiver_input=receiver_input,
            broadcast_from_receiver=broadcast_from_receiver,
            pool_to_receiver=pool_to_receiver,
            extra_receiver_ops={"softmax": extra_softmax},
            edge_mask=es.mask())

    def convolve(self, params, *, sender_node_input, sender_edge_input,
                 receiver_input, broadcast_from_receiver, pool_to_receiver,
                 extra_receiver_ops, edge_mask):  # pragma: no cover
        raise NotImplementedError


class SimpleConv(AnyToAnyConv):
    """message = message_fn(concat(sender inputs[, receiver state])),
    then reduce — the paper's Fig. 7 `MyConv` generalised.

    When the conv has the fused shape (node-to-node, sum-pooled, no edge
    feature, receiver state combined) it routes the whole
    gather->message-MLP->scatter round through the Pallas `edge_mpnn`
    kernel via `repro.kernels.dispatch`; otherwise (or when dispatch deems
    the call ineligible) it runs the generic broadcast/pool path.
    """

    def __init__(self, units: int, in_dim: int, *, reduce_type: str = "sum",
                 combine_receiver: bool = True, activation: str = "relu",
                 **kwargs):
        super().__init__(**kwargs)
        self.reduce_type = reduce_type
        self.combine_receiver = combine_receiver
        self.message_fn = Linear(in_dim, units, kernel_axes=(None, None))
        self.activation_name = activation
        self.act = ACTIVATIONS[activation]

    def init(self, key):
        return {"message": self.message_fn.init(key)}

    def fused_decision(self, params, graph: GraphTensor,
                       edge_set_name: str) -> kernel_dispatch.Decision:
        """Dispatch decision for running this conv as one fused kernel."""
        if self.receiver_tag == CONTEXT:
            return kernel_dispatch.Decision(False, "context receiver")
        if self.sender_edge_feature is not None:
            return kernel_dispatch.Decision(False, "edge feature input")
        if self.sender_node_feature is None:
            return kernel_dispatch.Decision(False, "no sender node input")
        if not (self.combine_receiver and self.receiver_feature):
            return kernel_dispatch.Decision(False, "no receiver state")
        if self.reduce_type != "sum":
            return kernel_dispatch.Decision(
                False, f"{self.reduce_type} pooling not fused")
        es = graph.edge_sets[edge_set_name]
        sender_name, recv_name = self._fused_endpoints(es)
        h_src = graph.node_sets[sender_name][self.sender_node_feature]
        h_tgt = graph.node_sets[recv_name][self.receiver_feature]
        if h_src.ndim != 2 or h_tgt.ndim != 2:
            return kernel_dispatch.Decision(False, "non-2D node states")
        if h_src.dtype != h_tgt.dtype:
            # the generic path would promote via concat; keep it there
            return kernel_dispatch.Decision(False, "mixed state dtypes")
        w = params["message"]["w"]
        if w.shape[0] != h_src.shape[1] + h_tgt.shape[1]:
            return kernel_dispatch.Decision(False, "in_dim mismatch")
        # same inputs dispatch.edge_mpnn re-checks in __call__: capacities
        # as node counts, so the two decisions cannot diverge
        return kernel_dispatch.edge_mpnn_decision(
            graph.node_sets[sender_name].capacity,
            graph.node_sets[recv_name].capacity,
            h_src.shape[1], h_tgt.shape[1],
            w.shape[1], h_src.dtype, self.activation_name,
            n_edges=int(es.adjacency.source.shape[0]),
            sorted_ids=self._sorted_hint())

    def describe_dispatch(self, params, graph: GraphTensor,
                          edge_set_name: str) -> kernel_dispatch.Decision:
        """The dispatch decision a call on `graph` takes: the fused
        `edge_mpnn` one when eligible, else the generic path's pool into
        a node set, whose reason also says why fusion was off."""
        fused = self.fused_decision(params, graph, edge_set_name)
        if fused.use_kernel or self.receiver_tag == CONTEXT:
            return fused
        es = graph.edge_sets[edge_set_name]
        sender_name, recv_name = self._fused_endpoints(es)
        dtypes = [graph.node_sets[sender_name][self.sender_node_feature]
                  .dtype] if self.sender_node_feature else []
        if self.sender_edge_feature:
            dtypes.append(es[self.sender_edge_feature].dtype)
        if self.combine_receiver and self.receiver_feature:
            dtypes.append(
                graph.node_sets[recv_name][self.receiver_feature].dtype)
        units = params["message"]["w"].shape[1]
        pool = kernel_dispatch.segment_reduce_decision(
            (int(es.adjacency.source.shape[0]), units),
            jnp.result_type(*dtypes), graph.node_sets[recv_name].capacity,
            self.reduce_type, sorted_ids=self._sorted_hint())
        return dataclasses.replace(
            pool, reason=f"unfused ({fused.reason}); pool {pool.reason}")

    def _fused_endpoints(self, es):
        if self.receiver_tag == TARGET:
            return es.adjacency.source_name, es.adjacency.target_name
        return es.adjacency.target_name, es.adjacency.source_name

    def _sorted_hint(self):
        """The BatchPlan layout bit sorts edges by TARGET; a SOURCE
        receiver scatters by source ids, which that sort leaves unsorted."""
        return None if self.receiver_tag == TARGET else False

    def __call__(self, params, graph: GraphTensor, edge_set_name: str):
        if not self.fused_decision(params, graph, edge_set_name).use_kernel:
            return super().__call__(params, graph, edge_set_name)
        es = graph.edge_sets[edge_set_name]
        adj = es.adjacency
        sender_idx, recv_idx = ((adj.source, adj.target)
                                if self.receiver_tag == TARGET
                                else (adj.target, adj.source))
        sender_name, recv_name = self._fused_endpoints(es)
        h_src = graph.node_sets[sender_name][self.sender_node_feature]
        h_tgt = graph.node_sets[recv_name][self.receiver_feature]
        n_tgt = graph.node_sets[recv_name].capacity
        w = params["message"]["w"].astype(h_src.dtype)
        b = params["message"]["b"].astype(h_src.dtype)
        tgt = jnp.where(es.mask(), recv_idx, n_tgt)  # padding -> dropped
        return kernel_dispatch.edge_mpnn(
            h_src, h_tgt, sender_idx, tgt, w, b,
            n_src=graph.node_sets[sender_name].capacity, n_tgt=n_tgt,
            activation=self.activation_name,
            sorted_ids=self._sorted_hint())

    def convolve(self, params, *, sender_node_input, sender_edge_input,
                 receiver_input, broadcast_from_receiver, pool_to_receiver,
                 extra_receiver_ops, edge_mask):
        parts = []
        if sender_node_input is not None:
            parts.append(sender_node_input)
        if sender_edge_input is not None:
            parts.append(sender_edge_input)
        if self.combine_receiver and receiver_input is not None:
            parts.append(broadcast_from_receiver(receiver_input))
        msg = self.act(self.message_fn(params["message"],
                                       jnp.concatenate(parts, axis=-1)))
        return pool_to_receiver(msg, reduce_type=self.reduce_type)


class GCNConv(AnyToAnyConv):
    """Kipf & Welling graph convolution with 1/sqrt(d_u d_v) normalisation
    (paper Eq. 4).  Self-loops are the caller's choice (add_self_loops in
    the data layer); degree counts include only valid edges."""

    def __init__(self, units: int, in_dim: int, *, use_bias: bool = False,
                 edge_set_name: str | None = None, **kwargs):
        super().__init__(**kwargs)
        self.units = units
        self.w = Linear(in_dim, units, use_bias=use_bias,
                        kernel_axes=(None, None))

    def init(self, key):
        return {"w": self.w.init(key)}

    def __call__(self, params, graph: GraphTensor, edge_set_name: str):
        tag = self.receiver_tag
        es = graph.edge_sets[edge_set_name]
        sender_tag = _OTHER[tag]
        h = graph.node_sets[es.adjacency.source_name
                            if sender_tag == SOURCE else
                            es.adjacency.target_name][HIDDEN_STATE]
        wh = self.w(params["w"], h)
        deg_r = ops.node_degree(graph, edge_set_name, tag)
        deg_s = ops.node_degree(graph, edge_set_name, sender_tag)
        inv_r = jax.lax.rsqrt(jnp.maximum(deg_r, 1).astype(wh.dtype))
        inv_s = jax.lax.rsqrt(jnp.maximum(deg_s, 1).astype(wh.dtype))
        msg = ops.broadcast_node_to_edges(
            graph, edge_set_name, sender_tag,
            feature_value=wh * inv_s[:, None])
        pooled = ops.pool_edges_to_node(graph, edge_set_name, tag, "sum",
                                        feature_value=msg)
        return pooled * inv_r[:, None]

    def convolve(self, *a, **k):  # unified entry not used
        raise NotImplementedError


class SAGEConv(AnyToAnyConv):
    """GraphSAGE aggregator (mean or max-pool variants, Hamilton et al.)."""

    def __init__(self, units: int, in_dim: int, *,
                 aggregator: str = "mean", hidden: int | None = None,
                 **kwargs):
        super().__init__(**kwargs)
        self.aggregator = aggregator
        self.w = Linear(in_dim, units, use_bias=False,
                        kernel_axes=(None, None))
        self.pool_mlp = (Linear(in_dim, hidden or in_dim)
                         if aggregator == "pool" else None)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        p = {"w": self.w.init(k1)}
        if self.pool_mlp is not None:
            p["pool"] = self.pool_mlp.init(k2)
        return p

    def convolve(self, params, *, sender_node_input, sender_edge_input,
                 receiver_input, broadcast_from_receiver, pool_to_receiver,
                 extra_receiver_ops, edge_mask):
        msg = sender_node_input
        if self.aggregator == "pool":
            msg = jax.nn.relu(self.pool_mlp(params["pool"], msg))
            pooled = pool_to_receiver(msg, reduce_type="max")
        else:
            pooled = pool_to_receiver(msg, reduce_type="mean")
        return self.w(params["w"], pooled)


class GATv2Conv(AnyToAnyConv):
    """GATv2 attention conv — faithful port of the paper's Appendix A.4."""

    def __init__(self, num_heads: int, per_head_channels: int, in_dim: int,
                 *, edge_in_dim: int | None = None,
                 attention_activation: str = "leaky_relu",
                 activation: str = "relu", **kwargs):
        super().__init__(**kwargs)
        self.num_heads = num_heads
        self.per_head = per_head_channels
        out = num_heads * per_head_channels
        self.w_query = Linear(in_dim, out, kernel_axes=(None, None))
        self.w_sender_node = (Linear(in_dim, out, kernel_axes=(None, None))
                              if self.takes_sender_node_input else None)
        self.w_sender_edge = (
            Linear(edge_in_dim or in_dim, out, use_bias=False,
                   kernel_axes=(None, None))
            if self.takes_sender_edge_input else None)
        self.attention_activation = (
            (lambda x: jax.nn.leaky_relu(x, 0.2))
            if attention_activation == "leaky_relu"
            else ACTIVATIONS[attention_activation])
        self.act = ACTIVATIONS[activation]

    def init(self, key):
        ks = jax.random.split(key, 4)
        p = {"w_query": self.w_query.init(ks[0]),
             "attn_logits": Param(
                 jax.random.normal(ks[3], (self.num_heads, self.per_head))
                 * (self.per_head ** -0.5), (None, None))}
        if self.w_sender_node is not None:
            p["w_sender_node"] = self.w_sender_node.init(ks[1])
        if self.w_sender_edge is not None:
            p["w_sender_edge"] = self.w_sender_edge.init(ks[2])
        return p

    def _split(self, t):
        return t.reshape(*t.shape[:-1], self.num_heads, self.per_head)

    def convolve(self, params, *, sender_node_input, sender_edge_input,
                 receiver_input, broadcast_from_receiver, pool_to_receiver,
                 extra_receiver_ops, edge_mask):
        query = broadcast_from_receiver(
            self._split(self.w_query(params["w_query"], receiver_input)))
        value_terms = []
        if sender_node_input is not None:
            value_terms.append(self._split(
                self.w_sender_node(params["w_sender_node"],
                                   sender_node_input)))
        if sender_edge_input is not None:
            value_terms.append(self._split(
                self.w_sender_edge(params["w_sender_edge"],
                                   sender_edge_input)))
        value = sum(value_terms)
        feats = self.attention_activation(query + value)
        logits = jnp.einsum("...hc,hc->...h", feats,
                            params["attn_logits"].astype(feats.dtype))
        coef = extra_receiver_ops["softmax"](logits)
        messages = value * coef[..., None]
        pooled = pool_to_receiver(messages, reduce_type="sum")
        return self.act(pooled.reshape(*pooled.shape[:-2], -1))


class MultiHeadAttentionConv(AnyToAnyConv):
    """Transformer-style dot-product attention on edges (paper §4.3)."""

    def __init__(self, num_heads: int, per_head_channels: int, in_dim: int,
                 **kwargs):
        super().__init__(**kwargs)
        self.num_heads = num_heads
        self.per_head = per_head_channels
        out = num_heads * per_head_channels
        self.wq = Linear(in_dim, out, use_bias=False, kernel_axes=(None, None))
        self.wk = Linear(in_dim, out, use_bias=False, kernel_axes=(None, None))
        self.wv = Linear(in_dim, out, use_bias=False, kernel_axes=(None, None))

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wq": self.wq.init(k1), "wk": self.wk.init(k2),
                "wv": self.wv.init(k3)}

    def convolve(self, params, *, sender_node_input, sender_edge_input,
                 receiver_input, broadcast_from_receiver, pool_to_receiver,
                 extra_receiver_ops, edge_mask):
        q = broadcast_from_receiver(
            self._split(self.wq(params["wq"], receiver_input)))
        k = self._split(self.wk(params["wk"], sender_node_input))
        v = self._split(self.wv(params["wv"], sender_node_input))
        logits = (q * k).sum(-1) * (self.per_head ** -0.5)
        coef = extra_receiver_ops["softmax"](logits)
        pooled = pool_to_receiver(v * coef[..., None], reduce_type="sum")
        return pooled.reshape(*pooled.shape[:-2], -1)

    def _split(self, t):
        return t.reshape(*t.shape[:-1], self.num_heads, self.per_head)
