"""The HGT configuration's model: the program's `core.models.hgt`
against the plain per-edge reference (`bench/references/hgt.py`), its
message passing toward the root, and its FLOP counter; the other
configurations' models do not reach the code HGT brought."""
import re

import jax
import numpy as np
import pytest

from bench.harness import check, dataset
from bench.harness.program import BENCH, Program, load_module
from bench.references import common
from bench.tests.conftest import load
from bench.tests.test_bench_flops import COUNTS, EDGES
from bench.tests.test_bench_message_passing import NODE_SETS, _moved

ROOTS = range(1, 13)


def _tiny(cache, config="tiny_hgt"):
    """A tiny configuration's program (the HGT's by default), weights
    from seed 7, and 12 sampled subgraphs, merged and padded."""
    from repro.data import find_size_constraints, merge_and_pad
    from repro.data.sampling import sample_subgraph, seed_rng
    cfg = load("configs", config)
    store, _ = dataset.load_store(cfg["dataset"], cache)
    prog = Program(cfg, store)
    weights = jax.tree_util.tree_map(np.asarray, prog.make_weights(7))
    graphs = [sample_subgraph(store, prog.spec, r, seed_rng(0, r))
              for r in ROOTS]
    batch = merge_and_pad(graphs, find_size_constraints(graphs, len(graphs)))
    return cfg, store, prog, weights, graphs, batch


@pytest.fixture(scope="module")
def agreed(tiny_cache):
    """(program, reference) of the tiny HGT on 12 merged subgraphs from
    one set of seeded random weights: root logits, loss and the gradient
    of every leaf."""
    cfg, store, prog, weights, graphs, batch = _tiny(tiny_cache)
    labels = prog.task.labels(batch)
    apply, task = prog.apply_fn(), prog.task

    def program_loss(p):
        graph = prog.gnn(p["gnn"], prog.init_states(p["init"], batch))
        return task.loss_from_graph(p["head"], graph, labels)

    schema_edges = {n: (e.source, e.target)
                    for n, e in store.schema.edge_sets.items()}
    ref = load_module(BENCH / "references" / "hgt.py")
    plain = common.merge([check.plain_graph(g) for g in graphs],
                         schema_edges)
    plain_labels = plain["nodes"]["paper"]["labels"][plain["roots"]]

    def ref_logits(p):
        return ref.forward(p, plain, schema_edges=schema_edges,
                           model=cfg["model"])

    def ref_loss(p):
        return common.cross_entropy(ref_logits(p), plain_labels)

    with jax.default_matmul_precision("highest"):
        out = {"program": (np.asarray(jax.jit(apply)(weights, batch))
                           [:len(graphs)],
                           *jax.jit(jax.value_and_grad(program_loss))(
                               weights)),
               "reference": (np.asarray(jax.jit(ref_logits)(weights)),
                             *jax.jit(jax.value_and_grad(ref_loss))(
                                 weights))}
    return out


def test_program_matches_reference(agreed):
    """Both run in float32 with exact matmuls on the CPU; they differ
    only in the order of sums (the program applies W_att and W_msg per
    receiving node, the reference per edge; the softmax sums its relations
    in another order), which moves these numbers by 2e-7 to 5e-7
    relative here: 1e-5 leaves that room twentyfold."""
    p_logits, p_loss, p_grad = agreed["program"]
    r_logits, r_loss, r_grad = agreed["reference"]
    scale = np.abs(r_logits).max()
    assert np.abs(p_logits - r_logits).max() <= 1e-5 * scale
    assert abs(float(p_loss) - float(r_loss)) <= 1e-5 * abs(float(r_loss))
    p_norms, r_norms = check.leaf_norms(p_grad), check.leaf_norms(r_grad)
    assert p_norms.keys() == r_norms.keys()
    gaps = check.leaf_gaps(p_norms, r_norms)
    assert max(gaps.values()) <= 1e-5, check.worst_leaves(p_norms, r_norms)
    # a direction check on the deepest leaves the root hears, past norms
    # (the stack's first entry is the input adaptation)
    for path in (("gnn", "rounds", 0, "node_sets", "field_of_study", "w"),
                 ("gnn", "rounds", 1, "edge_sets", "has_topic", "att", "w"),
                 ("gnn", "rounds", 2, "node_sets", "paper", "q", "w")):
        a, b = p_grad, r_grad
        for k in path:
            a, b = a[k], b[k]
        assert check.direction_gap(a, b) <= 1e-5, path


def test_joint_softmax_is_not_per_relation(agreed, tiny_cache):
    """The program with a softmax per relation, not over all of a
    paper's relations at once, gives logits far from the reference's:
    the comparison above catches a program that normalises per edge
    set."""
    from repro.core import ops
    _, _, prog, weights, graphs, batch = _tiny(tiny_cache)
    original = ops.segment_softmax

    def per_set(graph, names, tag, *, feature_value):
        if isinstance(names, str):
            return original(graph, names, tag, feature_value=feature_value)
        return [original(graph, n, tag, feature_value=v)
                for n, v in zip(names, feature_value)]

    ops.segment_softmax = per_set
    try:
        with jax.default_matmul_precision("highest"):
            apart = np.asarray(jax.jit(prog.apply_fn())(weights, batch))
    finally:
        ops.segment_softmax = original
    logits = agreed["reference"][0]
    assert np.abs(apart[:len(graphs)] - logits).max() > 1e-3 * np.abs(
        logits).max()


def test_root_hears_its_neighbours(tiny_cache):
    """Messages flow toward the root (receiver_tag source) and 2 rounds
    reach it from every node set: fields through the cited papers,
    institutions through its authors."""
    moved = _moved("tiny_hgt", tiny_cache)
    for ns in NODE_SETS:
        assert moved[ns] >= 6, (ns, moved)


def test_forward_flops_hand_count():
    mod = load_module(BENCH / "models" / "hgt.py")
    m = {"embedding_dim": 8, "hidden_dim": 6, "per_head": 3,
         "num_rounds": 2, "receiver_tag": "source"}
    init = 2 * 10 * 3 * 8
    inputs = 2 * 10 * 8 * 6 + 2 * 4 * 8 * 6
    # toward sources: papers hear over cites and written, authors over
    # writes; both sets send (K, V) and receive (Q, A)
    projections = 4 * (2 * 10 * 6 * 6) + 4 * (2 * 4 * 6 * 6)
    # per relation: W_att and W_msg per receiving node (6 x 3 each), a
    # score and a weighted value per edge (2 x 6 each)
    relations = (2 * 2 * 10 * 6 * 3 + 2 * 2 * 6 * 6) \
        + (2 * 2 * 10 * 6 * 3 + 2 * 2 * 5 * 6) \
        + (2 * 2 * 4 * 6 * 3 + 2 * 2 * 3 * 6)
    head = 2 * 2 * 6 * 5
    assert mod.forward_flops(m, EDGES, 3, 5, COUNTS) == \
        init + inputs + 2 * (projections + relations) + head


def _grad_hlo(prog, weights, batch) -> str:
    """Optimized HLO of the program's loss and gradient, metadata aside."""
    labels = prog.task.labels(batch)

    def loss(p):
        graph = prog.gnn(p["gnn"], prog.init_states(p["init"], batch))
        return prog.task.loss_from_graph(p["head"], graph, labels)
    hlo = jax.jit(jax.value_and_grad(loss)).lower(weights).compile().as_text()
    return re.sub(r", metadata=\{[^}]*\}", "", hlo.split("\nFileNames\n")[0])


@pytest.mark.parametrize("config", ["tiny_mpnn", "tiny_rgcn"])
def test_other_models_do_not_reach_the_new_code(config, tiny_cache,
                                                 monkeypatch):
    """`mag_mpnn`'s and `mag_rgcn`'s models call neither the softmax that
    now spans edge sets nor the rational tanh and sigmoid: with each made
    to fail when called they compile to the same HLO, so their compiled
    steps are what they were before HGT came."""
    from repro.core import graph_update, models, ops
    from repro.nn import layers
    _, _, prog, weights, _, batch = _tiny(tiny_cache, config)
    before = _grad_hlo(prog, weights, batch)

    def fail(*a, **kw):
        raise AssertionError("reached code that only HGT calls")
    for mod, name in ((ops, "segment_softmax"), (ops, "_joint_softmax"),
                      (layers, "rational_tanh"),
                      (layers, "rational_sigmoid"),
                      (models, "rational_tanh"),
                      (graph_update, "rational_sigmoid")):
        monkeypatch.setattr(mod, name, fail)
    assert _grad_hlo(prog, weights, batch) == before
