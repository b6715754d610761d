"""The FLOP counters of `mfu.*`, against a hand count at a small size."""
from bench.harness.program import BENCH, load_module

EDGES = {"cites": ("paper", "paper"), "writes": ("author", "paper"),
         "written": ("paper", "author")}
COUNTS = {"nodes": {"paper": 10, "author": 4},
          "edges": {"cites": 6, "writes": 3, "written": 5},
          "components": 2}


def test_vanilla_mpnn_hand_count():
    mod = load_module(BENCH / "models" / "vanilla_mpnn.py")
    m = {"embedding_dim": 8, "message_dim": 4, "hidden_dim": 6,
         "num_rounds": 2, "receiver_tag": "target"}
    init = 2 * 10 * 3 * 8
    # round 0: width 8; paper <- cites, writes; author <- written
    r0 = (2 * 6 * 16 * 4 + 6 * 4) + (2 * 3 * 16 * 4 + 3 * 4) \
        + 2 * 10 * (8 + 2 * 4) * 6 \
        + (2 * 5 * 16 * 4 + 5 * 4) + 2 * 4 * (8 + 4) * 6
    r1 = (2 * 6 * 12 * 4 + 6 * 4) + (2 * 3 * 12 * 4 + 3 * 4) \
        + 2 * 10 * (6 + 2 * 4) * 6 \
        + (2 * 5 * 12 * 4 + 5 * 4) + 2 * 4 * (6 + 4) * 6
    head = 2 * 2 * 6 * 5
    assert mod.forward_flops(m, EDGES, 3, 5, COUNTS) == init + r0 + r1 + head


def test_vanilla_mpnn_hand_count_toward_sources():
    """Pooled at each edge's source, the edge sets change receivers:
    papers hear over cites and written, authors over writes."""
    mod = load_module(BENCH / "models" / "vanilla_mpnn.py")
    m = {"embedding_dim": 8, "message_dim": 4, "hidden_dim": 6,
         "num_rounds": 2, "receiver_tag": "source"}
    init = 2 * 10 * 3 * 8
    r0 = (2 * 6 * 16 * 4 + 6 * 4) + (2 * 5 * 16 * 4 + 5 * 4) \
        + 2 * 10 * (8 + 2 * 4) * 6 \
        + (2 * 3 * 16 * 4 + 3 * 4) + 2 * 4 * (8 + 4) * 6
    r1 = (2 * 6 * 12 * 4 + 6 * 4) + (2 * 5 * 12 * 4 + 5 * 4) \
        + 2 * 10 * (6 + 2 * 4) * 6 \
        + (2 * 3 * 12 * 4 + 3 * 4) + 2 * 4 * (6 + 4) * 6
    head = 2 * 2 * 6 * 5
    assert mod.forward_flops(m, EDGES, 3, 5, COUNTS) == init + r0 + r1 + head


def test_rgcn_hand_count():
    mod = load_module(BENCH / "models" / "rgcn.py")
    m = {"embedding_dim": 8, "hidden_dim": 6, "num_rounds": 2}
    init = 2 * 10 * 3 * 8
    # per round: pool adds per edge and width, a linear of the pooled
    # state per receiver and edge set, a self linear per node
    r0 = (6 * 8 + 2 * 10 * 8 * 6) + (3 * 8 + 2 * 10 * 8 * 6) \
        + 2 * 10 * 8 * 6 + (5 * 8 + 2 * 4 * 8 * 6) + 2 * 4 * 8 * 6
    r1 = (6 * 6 + 2 * 10 * 6 * 6) + (3 * 6 + 2 * 10 * 6 * 6) \
        + 2 * 10 * 6 * 6 + (5 * 6 + 2 * 4 * 6 * 6) + 2 * 4 * 6 * 6
    head = 2 * 2 * 6 * 5
    assert mod.forward_flops(m, EDGES, 3, 5, COUNTS) == init + r0 + r1 + head
