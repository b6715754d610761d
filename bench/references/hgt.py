"""Plain reference of the Heterogeneous Graph Transformer (Hu, Dong,
Wang, Sun, WWW 2020, Eq. 1-5), computed per edge.

Each edge has a receiving end by the configuration's ``receiver_tag``.
Every node set's initial state (as `vanilla_mpnn`'s reference) first
goes through tanh(W x + b), with the stack's first entry's weights.
Per later round, for an edge e = (s, t) of relation r, t its receiving
end, and head i of width d:

    K_i(s) = (W_K[type s] h_s + b)_i, V_i(s) likewise, Q_i(t) with W_Q;
    ATT_i(e) = (K_i(s) W_att[r, i]) . Q_i(t) * mu[r, i] / sqrt(d);
    MSG_i(e) = V_i(s) W_msg[r, i];
    H^_i(t)  = sum over every edge e into t, in all of t's relations at
               once, of softmax_i(ATT)(e) MSG_i(e);
    H'(t)    = LayerNorm(sigmoid(alpha) (W_A GELU(H^(t)) + b)
                         + (1 - sigmoid(alpha)) H(t)),

GELU exact (with erf).  A node set that receives over no edge set keeps
its state.  Logits: a linear head on each root's last state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.scipy.special import erf

from bench.references import common


def _per_head(x, w, precision):
    """x [E, H, d] times w [d, H, d'], head by head."""
    return jnp.stack([common.mm(x[:, i], w[:, i], precision)
                      for i in range(w.shape[1])], axis=1)


def _gelu(x):
    return 0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))


def forward(p, batch, *, schema_edges, model, precision="highest"):
    back = {"source": True, "target": False}[model["receiver_tag"]]
    heads, d = int(model["num_heads"]), int(model["per_head"])
    nodes = batch["nodes"]
    h0 = {"paper": jax.nn.relu(common.linear(
        p["init"]["paper"], nodes["paper"]["feat"], precision))}
    for ns, table in p["init"].items():
        if ns != "paper":
            h0[ns] = common.gather(table["table"], nodes[ns]["id"])
    inputs, *rounds = p["gnn"]["rounds"]
    h = {ns: jnp.tanh(common.linear(w, h0[ns], precision))
         for ns, w in inputs["node_sets"].items()}

    def split(x):
        return x.reshape(x.shape[0], heads, d)

    for rnd in rounds:
        types, rels = rnd["node_sets"], rnd["edge_sets"]
        new = {}
        for ns, t in types.items():
            if "q" not in t:
                continue
            q_t = split(common.linear(t["q"], h[ns], precision))
            scores, msgs, at, valid = [], [], [], []
            for es in sorted(rels):
                send_ns, recv_ns = schema_edges[es]
                send, recv = "src", "tgt"
                if back:
                    send_ns, recv_ns, send, recv = recv_ns, send_ns, recv, send
                if recv_ns != ns:
                    continue
                e, r = batch["edges"][es], rels[es]
                s = types[send_ns]
                k = common.gather(split(common.linear(s["k"], h[send_ns],
                                                      precision)), e[send])
                v = common.gather(split(common.linear(s["v"], h[send_ns],
                                                      precision)), e[send])
                q = common.gather(q_t, e[recv])
                att = _per_head(k, r["att"]["w"], precision)
                scores.append(jnp.sum(att * q, axis=-1)
                              * r["prior"]["scale"] / math.sqrt(d))
                msgs.append(_per_head(v, r["msg"]["w"], precision))
                at.append(e[recv])
                valid.append(e["valid"])
            # the joint softmax, written out: one max and one sum per
            # receiver and head over its edges in all relations
            n = h[ns].shape[0]
            ids = jnp.where(jnp.concatenate(valid), jnp.concatenate(at), n)
            score = jnp.concatenate(scores)
            top = jax.lax.stop_gradient(
                jax.ops.segment_max(score, ids, num_segments=n + 1))
            ex = jnp.exp(score - top[ids])
            total = jax.ops.segment_sum(ex, ids, num_segments=n + 1)
            coef = ex / total[ids]
            pooled = jax.ops.segment_sum(
                coef[..., None] * jnp.concatenate(msgs), ids,
                num_segments=n + 1)[:n]
            upd = common.linear(t["a"], _gelu(pooled.reshape(n, heads * d)),
                                precision)
            alpha = jax.nn.sigmoid(t["skip"]["scale"])
            out = alpha * upd + (1 - alpha) * h[ns]
            new[ns] = common.layer_norm(t["norm"], out) if "norm" in t \
                else out
        h.update(new)
    return common.linear(p["head"], common.gather(h["paper"],
                                                  batch["roots"]), precision)
