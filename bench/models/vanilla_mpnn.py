"""The TF-GNN paper's §8 VanillaMPNN: the program's model and its FLOPs."""
from __future__ import annotations


def receiving(m: dict, edges: dict) -> dict:
    """{edge set: (sender, receiver) node sets} by the configuration's
    ``receiver_tag``: ``target`` pools at each edge's target, ``source``
    at its source (messages flow back along the sampled edges, toward
    the root that sampling grew out from)."""
    tag = m["receiver_tag"]
    if tag not in ("source", "target"):
        raise ValueError(f"receiver_tag {tag!r}: 'source' or 'target'")
    return {es: (tgt, src) if tag == "source" else (src, tgt)
            for es, (src, tgt) in edges.items()}


def program_gnn(m: dict, edges: dict, node_dims: dict):
    from repro.core import SOURCE, TARGET
    from repro.core.models import vanilla_mpnn
    tag = {"source": SOURCE, "target": TARGET}[m["receiver_tag"]]
    return vanilla_mpnn(edges, node_dims,
                        message_dim=int(m["message_dim"]),
                        hidden_dim=int(m["hidden_dim"]),
                        num_rounds=int(m["num_rounds"]),
                        reduce_type=m["reduce_type"],
                        receiver_tag=tag,
                        use_layer_norm=bool(m["use_layer_norm"]))


def forward_flops(m: dict, edges: dict, feat_dim: int, n_classes: int,
                  counts: dict) -> float:
    """Multiply-adds (x2) and pooling adds of one forward pass over the
    real nodes and edges in `counts` ({"nodes": {set: n}, "edges": {set:
    n}, "components": roots}).  Elementwise work (bias, ReLU, layer norm)
    is left out, as is the embedding lookup."""
    d, msg, hid = int(m["embedding_dim"]), int(m["message_dim"]), \
        int(m["hidden_dim"])
    nodes, es_n = counts["nodes"], counts["edges"]
    pairs = receiving(m, edges)
    flops = 2.0 * nodes.get("paper", 0) * feat_dim * d
    for rnd in range(int(m["num_rounds"])):
        width = d if rnd == 0 else hid
        for ns in nodes:
            incoming = [es for es, (_, recv) in pairs.items() if recv == ns]
            if not incoming:
                continue
            for es in incoming:
                e = es_n.get(es, 0)
                flops += 2.0 * e * (2 * width) * msg + e * msg
            flops += 2.0 * nodes[ns] * (width + msg * len(incoming)) * hid
    flops += 2.0 * counts["components"] * hid * n_classes
    return flops
