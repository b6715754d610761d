"""Plain reference of the TF-GNN paper's §8 VanillaMPNN (Fig. 7/8).

Each edge has a receiving end, by the configuration's ``receiver_tag``:
its target (``target``) or its source (``source``, so that messages flow
back along the sampled edges toward the root).  Per round, every node
set that receives over some edge set is updated from the states before
the round: for each of its edge sets (in name order), message =
ReLU(W [h_sender; h_receiver] + b) per edge, summed at the receiver;
next state = LayerNorm(ReLU(W' [h_old; pooled...] + b')).  Initial
states: ReLU(W feat + b) for papers, a table row for the rest.  Logits:
a linear head on each root's last state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.references import common


def forward(p, batch, *, schema_edges, model, precision="highest"):
    back = {"source": True, "target": False}[model["receiver_tag"]]
    nodes = batch["nodes"]
    h = {"paper": jax.nn.relu(common.linear(
        p["init"]["paper"], nodes["paper"]["feat"], precision))}
    for ns, table in p["init"].items():
        if ns != "paper":
            h[ns] = common.gather(table["table"], nodes[ns]["id"])
    for rnd in p["gnn"]["rounds"]:
        new = {}
        for ns, upd in rnd["node_sets"].items():
            pooled = []
            for es in sorted(upd["convs"]):
                send_ns, recv_ns = schema_edges[es]
                send, recv = "src", "tgt"
                if back:
                    send_ns, recv_ns, send, recv = recv_ns, send_ns, recv, send
                assert recv_ns == ns, (es, ns)
                e = batch["edges"][es]
                x = jnp.concatenate([common.gather(h[send_ns], e[send]),
                                     common.gather(h[recv_ns], e[recv])], -1)
                msg = jax.nn.relu(common.linear(
                    upd["convs"][es]["message"], x, precision))
                pooled.append(common.segment_sum(msg, e, h[ns].shape[0],
                                                 at=recv))
            ns_p = upd["next_state"]
            y = jax.nn.relu(common.linear(
                ns_p["dense"], jnp.concatenate([h[ns]] + pooled, -1),
                precision))
            new[ns] = common.layer_norm(ns_p["norm"], y) \
                if "norm" in ns_p else y
        h.update(new)
    return common.linear(p["head"], common.gather(h["paper"],
                                                  batch["roots"]), precision)
