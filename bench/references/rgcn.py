"""Plain reference of R-GCN (Schlichtkrull et al. 2018) as the repo
states it (paper Eq. 5): per round, every node set that some edge set
targets gets ReLU(sum over incoming edge sets of W_r mean(h_sender) +
W_self h_old), from the states before the round; no biases.  Initial
states and head as in `vanilla_mpnn`."""
from __future__ import annotations

import jax

from bench.references import common


def forward(p, batch, *, schema_edges, model, precision="highest"):
    del model   # the repo's R-GCN pools at each edge's target only
    nodes = batch["nodes"]
    h = {"paper": jax.nn.relu(common.linear(
        p["init"]["paper"], nodes["paper"]["feat"], precision))}
    for ns, table in p["init"].items():
        if ns != "paper":
            h[ns] = common.gather(table["table"], nodes[ns]["id"])
    for rnd in p["gnn"]["rounds"]:
        new = {}
        for ns, upd in rnd["node_sets"].items():
            total = common.mm(h[ns], upd["next_state"]["w_self"]["w"],
                              precision)
            for es in sorted(upd["convs"]):
                src_ns, tgt_ns = schema_edges[es]
                e = batch["edges"][es]
                mean = common.segment_mean(
                    common.gather(h[src_ns], e["src"]), e,
                    h[tgt_ns].shape[0])
                total = total + common.mm(mean, upd["convs"][es]["w"]["w"],
                                          precision)
            new[ns] = jax.nn.relu(total)
        h.update(new)
    return common.linear(p["head"], common.gather(h["paper"],
                                                  batch["roots"]), precision)
