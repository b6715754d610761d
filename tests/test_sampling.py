"""Sampler tests: Algorithm 1 invariants + SamplingSpecBuilder structure,
the batched core against the per-root sampler it replaced, and the replay
of `Generator.choice` from raw words."""
import numpy as np
import pytest

from repro.core.schema import mag_schema
from repro.data.sampling import (RANDOM_UNIFORM, TOP_K, InMemorySampler,
                                 SamplingSpecBuilder, sample_subgraph)
from repro.data.synthetic import synthetic_mag


def build_spec(schema):
    seed_op = SamplingSpecBuilder(schema).seed("paper")
    cited = seed_op.sample(8, "cites")
    authors = cited.join([seed_op]).sample(4, "written")
    author_papers = authors.sample(4, "writes")
    affil = authors.sample(4, "affiliated_with")
    topics = author_papers.join([seed_op, cited]).sample(4, "has_topic")
    return seed_op.build()


def test_spec_builder_matches_paper_fig6():
    spec = build_spec(mag_schema())
    names = [op.op_name for op in spec.sampling_ops]
    assert names[0] == "SEED->paper->paper"
    assert "author" in names[1]
    assert spec.sampling_ops[1].input_op_names == (
        "SEED->paper->paper", "SEED->paper")
    assert spec.sampling_ops[-1].edge_set_name == "has_topic"


def test_subgraph_invariants():
    store, _ = synthetic_mag(n_papers=300, n_authors=120,
                             n_institutions=10, n_fields=30)
    spec = build_spec(mag_schema())
    rng = np.random.default_rng(0)
    for seed in (0, 7, 123):
        g = sample_subgraph(store, spec, seed, rng)
        # root-first convention
        # (root paper is index 0 of the paper node set)
        feats = np.asarray(g.node_sets["paper"]["feat"])
        np.testing.assert_array_equal(
            feats[0], store.node_features["paper"]["feat"][seed])
        # fanout bounds: sampled cites per paper <= 8
        es = g.edge_sets["cites"]
        src = np.asarray(es.adjacency.source[:int(es.sizes.sum())])
        if len(src):
            _, counts = np.unique(src, return_counts=True)
            assert counts.max() <= 8
        # all edges reference in-range nodes
        for name, e in g.edge_sets.items():
            n_src = g.node_sets[e.adjacency.source_name].capacity
            n_tgt = g.node_sets[e.adjacency.target_name].capacity
            ne = int(np.asarray(e.sizes).sum())
            if ne:
                assert np.asarray(e.adjacency.source[:ne]).max() < n_src
                assert np.asarray(e.adjacency.target[:ne]).max() < n_tgt
        # dedup: no node appears twice
        ids = np.asarray(g.node_sets["paper"].sizes).sum()
        assert ids == g.node_sets["paper"].capacity


def test_sampler_determinism():
    store, _ = synthetic_mag(n_papers=200, n_authors=80, n_institutions=8,
                             n_fields=20)
    spec = build_spec(mag_schema())
    s1 = InMemorySampler(store, spec, seed=42).sample([3, 5])
    s2 = InMemorySampler(store, spec, seed=42).sample([3, 5])
    np.testing.assert_array_equal(
        np.asarray(s1[0].edge_sets["cites"].adjacency.source),
        np.asarray(s2[0].edge_sets["cites"].adjacency.source))


def _node_ids(graph, node_set):
    """Sorted global ids of a sampled graph's node set — the 'id' feature
    where present (author/institution/field), else feature rows hashed
    (paper carries 'feat', not 'id')."""
    ns = graph.node_sets[node_set]
    if "id" in ns.features:
        return sorted(np.asarray(ns["id"]).tolist())
    key = next(iter(sorted(ns.features)))
    return sorted(map(tuple, np.asarray(ns[key]).reshape(ns.capacity, -1)
                      .tolist()))


def test_distributed_sample_invariant_to_shard_count(tmp_path):
    """Regression (ISSUE 3 satellite): for a fixed base seed the sampled
    subgraphs — pinned down to the node sets of every rooted subgraph —
    must not depend on how many workers/shards drew them.  Each root draws
    from seed_rng(base_seed, root), so any partition yields the same
    output; only the grouping into shard files may differ."""
    from repro.data import distributed_sample, load_graphs
    from repro.data.sampling import seed_rng

    store, _ = synthetic_mag(n_papers=150, n_authors=60, n_institutions=6,
                             n_fields=15)
    spec = build_spec(mag_schema())
    seeds = list(range(40))

    def sample_with(num_shards):
        out = tmp_path / f"shards_{num_shards}"
        paths = distributed_sample(store, spec, seeds, str(out),
                                   num_shards=num_shards, base_seed=7)
        by_root = {}
        for shard, p in enumerate(paths):
            for root, g in zip(seeds[shard::num_shards], load_graphs(p)):
                by_root[root] = g
        return by_root

    ref = sample_with(1)
    for num_shards in (2, 4, 5):
        got = sample_with(num_shards)
        assert set(got) == set(ref)
        for root in seeds:
            for ns in ("paper", "author", "field_of_study"):
                assert _node_ids(got[root], ns) == _node_ids(ref[root], ns), \
                    (num_shards, root, ns)
            np.testing.assert_array_equal(
                np.asarray(got[root].edge_sets["cites"].adjacency.source),
                np.asarray(ref[root].edge_sets["cites"].adjacency.source))

    # the in-memory sampler follows the same convention: order-independent
    # and equal to the persisted shards for the same base seed
    mem = InMemorySampler(store, spec, seed=7)
    fwd = mem.sample(seeds[:6])
    rev = mem.sample(seeds[:6][::-1])[::-1]
    for a, b in zip(fwd, rev):
        assert _node_ids(a, "paper") == _node_ids(b, "paper")
    for root, g in zip(seeds[:6], fwd):
        assert _node_ids(g, "paper") == _node_ids(ref[root], "paper")


# ---------------------------------------------------------------------------
# The batched core against the per-root sampler it replaced
# ---------------------------------------------------------------------------

def frozen_sample_subgraph(store, spec, seed, rng):
    """Test-only oracle: the per-root, per-node Algorithm 1 that
    `sample_subgraphs` replaced, frozen as it was.  The batched core must
    give exactly its subgraphs and generator end states."""
    from repro.core.graph_tensor import (Adjacency, Context, EdgeSet,
                                         GraphTensor, NodeSet)
    op_nodes = {spec.seed_op_name: np.asarray([seed], np.int64)}
    edges = {}
    for op in spec.sampling_ops:
        frontier = np.unique(np.concatenate([
            op_nodes[name] for name in op.input_op_names]))
        out_nodes = []
        for u, nbrs in zip(frontier,
                           store.neighbors_batch(op.edge_set_name, frontier)):
            if len(nbrs) == 0:
                continue
            if len(nbrs) > op.sample_size:
                if op.strategy == RANDOM_UNIFORM:
                    nbrs = rng.choice(nbrs, op.sample_size, replace=False)
                else:
                    nbrs = nbrs[:op.sample_size]
            out_nodes.append(nbrs)
            edges.setdefault(op.edge_set_name, []).extend(
                (int(u), int(v)) for v in nbrs)
        op_nodes[op.op_name] = (np.unique(np.concatenate(out_nodes))
                                if out_nodes else np.asarray([], np.int64))
    nodes_per_set = {spec.seed_node_set: {seed}}
    for op in spec.sampling_ops:
        es = store.schema.edge_sets[op.edge_set_name]
        nodes_per_set.setdefault(es.source, set())
        nodes_per_set.setdefault(es.target, set())
        for (u, v) in edges.get(op.edge_set_name, []):
            nodes_per_set[es.source].add(u)
            nodes_per_set[es.target].add(v)
    id_maps = {}
    for ns_name, ids in nodes_per_set.items():
        ordered = sorted(ids)
        if ns_name == spec.seed_node_set:
            ordered = [seed] + [i for i in ordered if i != seed]
        id_maps[ns_name] = {gid: i for i, gid in enumerate(ordered)}
    node_sets = {}
    for ns_name, id_map in id_maps.items():
        gids = np.fromiter(id_map.keys(), np.int64, len(id_map))
        feats = store.gather_node_features(ns_name, gids)
        node_sets[ns_name] = NodeSet(
            np.asarray([len(gids)], np.int32), feats, len(gids))
    empty = lambda es: EdgeSet(  # noqa: E731
        np.asarray([0], np.int32),
        Adjacency(np.zeros(1, np.int32), np.zeros(1, np.int32),
                  es.source, es.target), {}, 1)
    edge_sets = {}
    for es_name, pairs in edges.items():
        es = store.schema.edge_sets[es_name]
        uniq = sorted(set(pairs))
        src = np.asarray([id_maps[es.source][u] for u, _ in uniq], np.int32)
        tgt = np.asarray([id_maps[es.target][v] for _, v in uniq], np.int32)
        edge_sets[es_name] = EdgeSet(
            np.asarray([len(uniq)], np.int32),
            Adjacency(src, tgt, es.source, es.target), {},
            len(uniq)) if uniq else empty(es)
    for es_name, es in store.schema.edge_sets.items():
        if es_name not in edge_sets and es.source in id_maps \
                and es.target in id_maps:
            edge_sets[es_name] = empty(es)
    return GraphTensor(
        Context(np.asarray([1], np.int32), {}), node_sets, edge_sets)


def assert_same_subgraph(a, b):
    """Equal node and edge sets, in the same order, with equal sizes,
    capacities, features (node ids and labels among them), adjacency and
    dtypes."""
    assert list(a.node_sets) == list(b.node_sets)
    assert list(a.edge_sets) == list(b.edge_sets)
    np.testing.assert_array_equal(a.context.sizes, b.context.sizes)
    for name, x in a.node_sets.items():
        y = b.node_sets[name]
        assert x.capacity == y.capacity, name
        assert list(x.features) == list(y.features), name
        for arr, ref in [(x.sizes, y.sizes)] + [
                (x.features[f], y.features[f]) for f in x.features]:
            assert arr.dtype == ref.dtype, name
            np.testing.assert_array_equal(arr, ref, err_msg=name)
    for name, x in a.edge_sets.items():
        y = b.edge_sets[name]
        assert x.capacity == y.capacity, name
        assert (x.adjacency.source_name, x.adjacency.target_name) == (
            y.adjacency.source_name, y.adjacency.target_name)
        for arr, ref in ((x.sizes, y.sizes),
                         (x.adjacency.source, y.adjacency.source),
                         (x.adjacency.target, y.adjacency.target)):
            assert arr.dtype == ref.dtype, name
            np.testing.assert_array_equal(arr, ref, err_msg=name)


def mag_rgcn_spec(schema, strategy=RANDOM_UNIFORM):
    """The OGB R-GCN baseline's spec (fanouts 25 then 20)."""
    seed_op = SamplingSpecBuilder(schema, strategy).seed("paper")
    papers = seed_op.sample(25, "cites")
    authors = seed_op.sample(25, "written")
    papers.join([seed_op]).sample(20, "cites")
    papers.sample(20, "written")
    papers.join([seed_op]).sample(20, "has_topic")
    authors.sample(20, "writes")
    authors.sample(20, "affiliated_with")
    return seed_op.build()


def mag_mpnn_spec(schema, strategy=RANDOM_UNIFORM):
    """The TF-GNN paper's OGBN-MAG spec (fanouts 8/4/4/4/4, Fig. 6)."""
    seed_op = SamplingSpecBuilder(schema, strategy).seed("paper")
    cited = seed_op.sample(8, "cites")
    authors = cited.join([seed_op]).sample(4, "written")
    author_papers = authors.sample(4, "writes")
    authors.sample(4, "affiliated_with")
    author_papers.join([seed_op, cited]).sample(4, "has_topic")
    return seed_op.build()


ISOLATED, HUB = 5, 6   # papers: no citation at all; 10,500 citations


@pytest.fixture(scope="module")
def dense_store():
    """A small MAG with degrees well above both specs' fanouts, one paper
    that cites nothing (an empty edge set) and one that cites 10,500
    times (a population past `choice`'s Floyd range)."""
    from repro.data.sampling import GraphStore
    store, _ = synthetic_mag(n_papers=600, n_authors=300, n_institutions=40,
                             n_fields=50, feat_dim=8, avg_cites=30,
                             avg_writes=8, avg_topics=25, seed=3)
    src, tgt = store.edges["cites"]
    keep = src != ISOLATED
    hub = np.random.default_rng(0).integers(0, 600, 10_500)
    edges = dict(store.edges)
    edges["cites"] = (np.concatenate([src[keep], np.full(10_500, HUB)]),
                      np.concatenate([tgt[keep], hub]))
    return GraphStore(store.schema, edges, store.node_features,
                      store.num_nodes)


@pytest.mark.parametrize("call", ["one_root", "many_roots", "shared_rng",
                                  "lexsort_keys"])
@pytest.mark.parametrize("strategy", [RANDOM_UNIFORM, TOP_K])
@pytest.mark.parametrize("make_spec", [mag_mpnn_spec, mag_rgcn_spec],
                         ids=["fanouts_8_4_4_4_4", "fanouts_25_20"])
def test_batched_sampling_matches_per_root_oracle(dense_store, make_spec,
                                                  strategy, call,
                                                  monkeypatch):
    """`sample_subgraphs` gives the frozen per-root sampler's subgraphs
    and generator end states, root for root, for roots in shuffled order:
    called root by root, over many roots, over many roots sharing one
    generator, and with keys too wide to pack (the lexsort path)."""
    from repro.data import sampling
    store = dense_store
    spec = make_spec(store.schema, strategy)
    roots = np.random.default_rng(11).permutation(600)[:40]
    roots = np.concatenate([[ISOLATED, HUB], roots[~np.isin(
        roots, [ISOLATED, HUB])]])
    roots = np.random.default_rng(12).permutation(roots)
    if call == "lexsort_keys":
        monkeypatch.setattr(sampling, "_KEY_LIMIT", 1000)
    if call == "shared_rng":
        mine, theirs = [np.random.default_rng(5)], [np.random.default_rng(5)]
        got = sampling.sample_subgraphs(store, spec, roots,
                                        mine * len(roots))
        want = [frozen_sample_subgraph(store, spec, int(r), theirs[0])
                for r in roots]
    else:
        mine = [sampling.seed_rng(9, r) for r in roots]
        theirs = [sampling.seed_rng(9, r) for r in roots]
        got = ([sampling.sample_subgraph(store, spec, int(r), g)
                for r, g in zip(roots, mine)] if call == "one_root" else
               sampling.sample_subgraphs(store, spec, roots, mine))
        want = [frozen_sample_subgraph(store, spec, int(r), g)
                for r, g in zip(roots, theirs)]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert_same_subgraph(g, w)
    for a, b in zip(mine, theirs):
        assert a.bit_generator.state == b.bit_generator.state
    empty = got[list(roots).index(ISOLATED)]
    assert int(empty.edge_sets["cites"].sizes[0]) == 0
    assert empty.edge_sets["cites"].capacity == 1


def _words_for(rng, pops, k):
    """The 32-bit words `rng` would feed the next len(pops) calls of
    ``choice(n, k, replace=False)``, read from a copy of it."""
    from repro.data.sampling import _draw_words
    twin = np.random.Generator(np.random.PCG64())
    twin.bit_generator.state = rng.bit_generator.state
    words, _ = _draw_words(twin.bit_generator, twin.bit_generator.state,
                           len(pops) * (2 * k - 1))
    return words.astype(np.uint64).reshape(len(pops), 2 * k - 1)


@pytest.mark.parametrize("k", [1, 2, 4, 8, 20, 25])
def test_replay_matches_generator_choice(k):
    """`_replay_choice` gives `Generator.choice(n, k, replace=False)`
    exactly over a grid of populations and seeds, and `_draw_choices`
    gives many calls in a row on one generator, carrying the held half
    word into native draws between its calls."""
    from repro.data.sampling import _draw_choices, _replay_choice
    pops = np.asarray([k + 1, k + 2, 2 * k + 1, 3 * k + 7, 100, 1000,
                       9999, 10_000])
    for seed in range(12):
        mine, theirs = np.random.default_rng(seed), np.random.default_rng(
            seed)
        picks, rejected = _replay_choice(_words_for(mine, pops, k), pops, k)
        assert not rejected.any()
        for n, p in zip(pops, picks):
            np.testing.assert_array_equal(p, theirs.choice(
                np.arange(n), k, replace=False))
        # many calls in a row, and native draws between them
        mine = np.random.default_rng(seed)
        theirs = np.random.default_rng(seed)
        for run in range(6):
            rows = np.random.default_rng([seed, run]).choice(pops, 31)
            got = _draw_choices([mine], np.zeros(len(rows), np.int64),
                                rows, k)
            for n, p in zip(rows, got):
                np.testing.assert_array_equal(p, theirs.choice(
                    n, k, replace=False))
            assert mine.integers(0, 2 ** 31 - 1) == theirs.integers(
                0, 2 ** 31 - 1)
            assert mine.bit_generator.state == theirs.bit_generator.state


def test_rejected_word_redoes_the_op_with_choice(monkeypatch):
    """A word Lemire's method would reject (forced here by zeroing the
    first word of one generator's stream) voids that generator's replayed
    rows: it is reset and calls ``choice``, and still matches; the other
    generator's rows stay replayed."""
    from repro.data import sampling
    draw_words = sampling._draw_words

    def zero_first(bit_generator, state, count):
        words, after = draw_words(bit_generator, state, count)
        if bit_generator is mine[1].bit_generator:
            words = words.copy()
            words[0] = 0        # 0 < 2**32 mod 13 = 9: rejected
        return words, after

    monkeypatch.setattr(sampling, "_draw_words", zero_first)
    mine = [np.random.default_rng(s) for s in (1, 2)]
    theirs = [np.random.default_rng(s) for s in (1, 2)]
    pops, k = np.asarray([20, 30, 20, 40]), 8   # first draw in [0, 12]
    before = sampling.sampling_counts()
    got = sampling._draw_choices(mine, np.asarray([0, 0, 1, 1]), pops, k)
    after = sampling.sampling_counts()
    assert after["root_ops_redone"] - before["root_ops_redone"] == 1
    assert after["draws_replayed"] - before["draws_replayed"] == 2
    for row, g in enumerate([0, 0, 1, 1]):
        np.testing.assert_array_equal(got[row], theirs[g].choice(
            pops[row], k, replace=False))
    for a, b in zip(mine, theirs):
        assert a.bit_generator.state == b.bit_generator.state


def test_population_past_floyd_range_uses_choice():
    """Past 10,000 `choice` may shuffle a tail instead of running Floyd's
    algorithm: such a generator's rows call ``choice`` and match, and so
    do generators other than PCG64."""
    from repro.data import sampling
    pops, k = np.asarray([300, 12_000, 400, 10_001, 260]), 250   # tails
    rows = np.asarray([0, 0, 1, 2, 2])
    for make in (np.random.default_rng,
                 lambda s: np.random.Generator(np.random.MT19937(s))):
        mine = [make(s) for s in (4, 5, 6)]
        theirs = [make(s) for s in (4, 5, 6)]
        before = sampling.sampling_counts()
        got = sampling._draw_choices(mine, rows, pops, k)
        after = sampling.sampling_counts()
        redone = after["root_ops_redone"] - before["root_ops_redone"]
        assert redone == (2 if make is np.random.default_rng else 3)
        for row, g in enumerate(rows):
            np.testing.assert_array_equal(got[row], theirs[g].choice(
                pops[row], k, replace=False))
        for a, b in zip(mine, theirs):
            np.testing.assert_equal(a.bit_generator.state,
                                    b.bit_generator.state)


@pytest.mark.parametrize("producer", ["store_provider", "sampler_worker"])
def test_step_batches_match_per_root_oracle(dense_store, producer):
    """`StoreProvider.epoch` and `SamplerWorker.build_step` sample each
    step's roots in one batched call; their batches equal a
    `BatcherProvider` over the frozen per-root sampler's subgraphs."""
    import jax
    from repro.data import find_size_constraints
    from repro.data.sampling import seed_rng
    from repro.orchestration import BatcherProvider, StoreProvider
    from repro.sampling_service.worker import SamplerWorker
    store = dense_store
    spec = mag_mpnn_spec(store.schema)
    roots = np.random.default_rng(21).permutation(600)[:48]
    roots[3], roots[17] = ISOLATED, HUB
    graphs = [frozen_sample_subgraph(store, spec, int(r), seed_rng(4, r))
              for r in roots]
    sizes = find_size_constraints(graphs, 8)
    want = BatcherProvider(graphs, 8, sizes, seed=1, num_replicas=2)
    if producer == "store_provider":
        sp = StoreProvider(store, spec, roots, batch_size=8, sizes=sizes,
                           seed=1, num_replicas=2, base_seed=4)
        got = {e: list(sp.epoch(e)) for e in (0, 1)}
    else:
        worker = SamplerWorker(0, None, store, spec, roots,
                               want.batcher.plan, sizes, base_seed=4)
        got = {e: [worker.build_step(e, s) for s in range(want.num_steps)]
               for e in (0, 1)}
    for epoch, batches in got.items():
        ref = list(want.epoch(epoch))
        assert len(batches) == len(ref) == want.num_steps
        for g, w in zip(batches, ref):
            la, lb = (jax.tree_util.tree_leaves(x) for x in (g, w))
            assert len(la) == len(lb)
            for x, y in zip(la, lb):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
