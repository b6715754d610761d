"""R-GCN (Schlichtkrull et al. 2018) as `repro.core.models.rgcn` builds
it: the program's model and its FLOPs."""
from __future__ import annotations


def program_gnn(m: dict, edges: dict, node_dims: dict):
    from repro.core.models import rgcn
    return rgcn(edges, node_dims, hidden_dim=int(m["hidden_dim"]),
                num_rounds=int(m["num_rounds"]))


def forward_flops(m: dict, edges: dict, feat_dim: int, n_classes: int,
                  counts: dict) -> float:
    """As `vanilla_mpnn.forward_flops`: per edge set a mean pool (one add
    per edge and width) and a linear map of the pooled state per
    receiving node, plus a self linear per node."""
    d, hid = int(m["embedding_dim"]), int(m["hidden_dim"])
    nodes, es_n = counts["nodes"], counts["edges"]
    flops = 2.0 * nodes.get("paper", 0) * feat_dim * d
    for rnd in range(int(m["num_rounds"])):
        width = d if rnd == 0 else hid
        for ns in nodes:
            incoming = [es for es, (_, tgt) in edges.items() if tgt == ns]
            if not incoming:
                continue
            for es in incoming:
                flops += es_n.get(es, 0) * width
                flops += 2.0 * nodes[ns] * width * hid
            flops += 2.0 * nodes[ns] * width * hid
    flops += 2.0 * counts["components"] * hid * n_classes
    return flops
