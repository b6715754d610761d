"""Which sampled node sets reach the root's logits: the cells do real
message passing.  The VanillaMPNN pools toward edge sources, so every
node set reaches the root; the repo's R-GCN pools toward edge targets
only, so the root hears its authors and never its fields or
institutions (the departure its configuration states)."""
import numpy as np
import pytest

import jax

from bench.harness import dataset
from bench.harness.program import Program
from bench.tests.conftest import load

NODE_SETS = ("paper", "author", "institution", "field_of_study")


def _moved(config, cache) -> dict:
    """{node set: roots, of 8, whose logits move when that node set's
    sampled inputs change (papers: every paper but the root)}."""
    from repro.data import find_size_constraints, merge_and_pad
    from repro.data.sampling import sample_subgraph, seed_rng
    cfg = load("configs", config)
    store, _ = dataset.load_store(cfg["dataset"], cache)
    prog = Program(cfg, store)
    weights = prog.make_weights(3)
    apply = jax.jit(prog.apply_fn())
    moved = dict.fromkeys(NODE_SETS, 0)
    for root in range(1, 9):
        g = sample_subgraph(store, prog.spec, root, seed_rng(0, root))
        sizes = find_size_constraints([g], 1)
        batch = merge_and_pad([g], sizes)
        base = np.asarray(apply(weights, batch))[0]
        for ns in NODE_SETS:
            n = g.node_sets[ns].capacity
            if ns == "paper":
                feats = dict(g.node_sets["paper"].features)
                feats["feat"] = np.array(feats["feat"])
                feats["feat"][1:] += 1.0
                out = apply(weights, merge_and_pad(
                    [g.replace_features(node_sets={"paper": feats})], sizes))
            else:
                table = weights["init"][ns]["table"]
                ids = np.asarray(batch.node_sets[ns]["id"][:n])
                bumped = dict(weights, init=dict(weights["init"], **{
                    ns: {"table": table.at[ids].add(1.0)}}))
                out = apply(bumped, batch)
            moved[ns] += int(np.abs(np.asarray(out)[0] - base).max() > 1e-4)
    return moved


@pytest.mark.parametrize("config", ["tiny_mpnn", "tiny_rgcn"])
def test_root_hears_its_neighbours(config, tiny_cache):
    """Each node set that reaches the root moves most roots' logits (a
    root may lack, say, authors); one that cannot reach it moves none."""
    moved = _moved(config, tiny_cache)
    reach = (NODE_SETS if config == "tiny_mpnn" else ("author",))
    for ns in NODE_SETS:
        if ns in reach:
            assert moved[ns] >= 6, (ns, moved)
        elif ns != "paper":
            assert moved[ns] == 0, (ns, moved)
