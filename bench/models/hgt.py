"""The Heterogeneous Graph Transformer (Hu, Dong, Wang, Sun, WWW 2020)
as `repro.core.models.hgt` builds it: the program's model and its
FLOPs."""
from __future__ import annotations


def receiving(m: dict, edges: dict) -> dict:
    """{edge set: (sender, receiver) node sets} by ``receiver_tag``."""
    back = {"source": True, "target": False}[m["receiver_tag"]]
    return {es: (tgt, src) if back else (src, tgt)
            for es, (src, tgt) in edges.items()}


def program_gnn(m: dict, edges: dict, node_dims: dict):
    from repro.core import SOURCE, TARGET
    from repro.core.models import hgt
    if int(m["num_heads"]) * int(m["per_head"]) != int(m["hidden_dim"]):
        raise ValueError("hidden_dim must be num_heads x per_head")
    return hgt(edges, node_dims, num_heads=int(m["num_heads"]),
               per_head=int(m["per_head"]),
               num_rounds=int(m["num_rounds"]),
               receiver_tag={"source": SOURCE,
                             "target": TARGET}[m["receiver_tag"]],
               use_layer_norm=bool(m["use_layer_norm"]))


def forward_flops(m: dict, edges: dict, feat_dim: int, n_classes: int,
                  counts: dict) -> float:
    """Multiply-adds (x2) of one forward pass over the real nodes and
    edges in `counts`, as the program computes them: the paper features'
    linear map and each node set's input adaptation; per round, K and V
    of every sending node set and Q and A of every receiving one, per
    node; per relation, W_att (on the receiver's queries) and W_msg (on
    the pooled values) per receiving node, and per edge the score's dot
    product and the weighted sum of values; the head.  Softmax, GELU,
    layer norm and the skip are left out, as is the embedding lookup."""
    d, hid = int(m["embedding_dim"]), int(m["hidden_dim"])
    per_head = int(m["per_head"])
    nodes, es_n = counts["nodes"], counts["edges"]
    pairs = receiving(m, edges)
    senders = {s for s, _ in pairs.values()}
    receivers = {r for _, r in pairs.values()}
    flops = 2.0 * nodes.get("paper", 0) * feat_dim * d
    flops += sum(2.0 * n * d * hid for n in nodes.values())
    for _ in range(int(m["num_rounds"])):
        for ns, n in nodes.items():
            projections = 2 * (ns in senders) + 2 * (ns in receivers)
            flops += projections * 2.0 * n * hid * hid
        for es, (_, recv) in pairs.items():
            relation = 2 * 2.0 * nodes.get(recv, 0) * hid * per_head
            flops += relation + 2 * 2.0 * es_n.get(es, 0) * hid
    flops += 2.0 * counts["components"] * hid * n_classes
    return flops
