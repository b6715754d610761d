"""Rooted subgraph sampling (paper §6.1 + Algorithm 1).

`SamplingSpecBuilder` is the paper's Fig. 6 fluent API; the produced
`SamplingSpec` drives both the in-memory sampler (§6.1.2) and the
distributed sampler (§6.1.1) — the latter implemented over an
embarrassingly-parallel shard interface: seeds are partitioned into shards,
each shard runs Algorithm 1 independently against the (read-only) graph
store and writes one output file, which is the unit of fault tolerance
(idempotent re-execution on worker failure, as with the paper's Flume
pipeline).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import os
import threading
from typing import Callable, Mapping, Optional, Sequence

import numpy as np

from repro.core.graph_tensor import (Adjacency, Context, EdgeSet,
                                     GraphTensor, NodeSet)
from repro.core.schema import GraphSchema

RANDOM_UNIFORM = "RANDOM_UNIFORM"
TOP_K = "TOP_K"


@dataclasses.dataclass(frozen=True)
class SamplingOp:
    op_name: str
    input_op_names: tuple[str, ...]
    edge_set_name: str
    sample_size: int
    strategy: str = RANDOM_UNIFORM


@dataclasses.dataclass(frozen=True)
class SamplingSpec:
    seed_node_set: str
    seed_op_name: str
    sampling_ops: tuple[SamplingOp, ...]


class _OpHandle:
    def __init__(self, builder: "SamplingSpecBuilder", op_name: str,
                 node_set: str):
        self.builder = builder
        self.op_name = op_name
        self.node_set = node_set

    def sample(self, sample_size: int, edge_set_name: str) -> "_OpHandle":
        return self.builder._add_op((self,), sample_size, edge_set_name)

    def join(self, others: Sequence["_OpHandle"]) -> "_JoinHandle":
        return _JoinHandle((self, *others), self.builder)

    def build(self) -> SamplingSpec:
        return self.builder._build()


class _JoinHandle:
    def __init__(self, handles, builder):
        self.handles = handles
        self.builder = builder

    def sample(self, sample_size: int, edge_set_name: str) -> _OpHandle:
        return self.builder._add_op(self.handles, sample_size, edge_set_name)


class SamplingSpecBuilder:
    """Fluent builder (paper Fig. 6)."""

    def __init__(self, schema: GraphSchema,
                 default_strategy: str = RANDOM_UNIFORM):
        self.schema = schema
        self.strategy = default_strategy
        self._ops: list[SamplingOp] = []
        self._seed: Optional[_OpHandle] = None

    def seed(self, node_set_name: str) -> _OpHandle:
        assert node_set_name in self.schema.node_sets
        self._seed = _OpHandle(self, f"SEED->{node_set_name}", node_set_name)
        return self._seed

    def _add_op(self, inputs, sample_size: int, edge_set_name: str):
        es = self.schema.edge_sets[edge_set_name]
        for h in inputs:
            assert h.node_set == es.source, \
                (f"edge set {edge_set_name} samples {es.source}->"
                 f"{es.target}, got input over {h.node_set}")
        op_name = (f"({'|'.join(h.op_name for h in inputs)})"
                   f"->{es.target}" if len(inputs) > 1 else
                   f"{inputs[0].op_name}->{es.target}")
        self._ops.append(SamplingOp(
            op_name, tuple(h.op_name for h in inputs), edge_set_name,
            sample_size, self.strategy))
        return _OpHandle(self, op_name, es.target)

    def _build(self) -> SamplingSpec:
        return SamplingSpec(self._seed.node_set, self._seed.op_name,
                            tuple(self._ops))


# ---------------------------------------------------------------------------
# Graph store + in-memory sampler
# ---------------------------------------------------------------------------

class GraphStore:
    """Adjacency-list store of the full (unsampled) heterogeneous graph.

    edges: {edge_set: (src_ids, tgt_ids)} (numpy int64)
    node_features: {node_set: {feature: np.ndarray [n, ...]}}
    """

    def __init__(self, schema: GraphSchema,
                 edges: Mapping[str, tuple[np.ndarray, np.ndarray]],
                 node_features: Mapping[str, Mapping[str, np.ndarray]],
                 num_nodes: Mapping[str, int]):
        self.schema = schema
        self.edges = dict(edges)
        self.node_features = {k: dict(v) for k, v in node_features.items()}
        self.num_nodes = dict(num_nodes)
        # CSR-ish index per edge set for O(deg) neighbor queries, built
        # lazily on first `neighbors` touch: a wide heterogeneous store
        # only pays the argsort for the edge sets a spec actually
        # samples (opening OGBN-MAG to sample `cites` must not index
        # `affiliated_with`)
        self._index: dict[str, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}

    def _reindex(self, name: str) -> None:
        """(Re)build one edge set's CSR index from `self.edges[name]` —
        the hook mutating subclasses (repro.serve.cache.VersionedGraphStore)
        call after editing an adjacency list."""
        src, tgt = self.edges[name]
        n_src = self.num_nodes[self.schema.edge_sets[name].source]
        order = np.argsort(src, kind="stable")
        sorted_src = src[order]
        starts = np.searchsorted(sorted_src, np.arange(n_src))
        ends = np.searchsorted(sorted_src, np.arange(n_src) + 1)
        self._index[name] = (starts, ends, tgt[order])

    def _csr(self, edge_set: str) -> tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
        if edge_set not in self._index:
            self._reindex(edge_set)
        return self._index[edge_set]

    def neighbors(self, edge_set: str, node: int) -> np.ndarray:
        starts, ends, tgts = self._csr(edge_set)
        return tgts[starts[node]:ends[node]]

    def neighbors_batch(self, edge_set: str,
                        nodes: Sequence[int]) -> list[np.ndarray]:
        """Neighbor lists for `nodes`, in order.  Partitioned stores
        (repro.storage.ShardedGraphStore) override this to batch
        cross-shard lookups into one request per peer instead of one
        round-trip per node."""
        return [self.neighbors(edge_set, int(u)) for u in nodes]

    def neighbors_flat(self, edge_set: str, nodes: Sequence[int]
                       ) -> tuple[np.ndarray, np.ndarray]:
        """The frontier-expansion hook: ``(counts, targets)``, each
        node's neighbour count and all their neighbour lists end to end,
        in the order of `nodes` (int64).  A store that overrides
        `neighbors_batch` overrides this to match."""
        starts, ends, tgts = self._csr(edge_set)
        nodes = np.asarray(nodes, np.int64)
        lo = np.asarray(starts[nodes], np.int64)
        counts = np.asarray(ends[nodes], np.int64) - lo
        pos = np.repeat(lo - (np.cumsum(counts) - counts), counts)
        return counts, np.asarray(tgts[pos + np.arange(len(pos))], np.int64)

    def gather_node_features(self, node_set: str,
                             ids: np.ndarray) -> dict[str, np.ndarray]:
        """Feature rows for `ids` of one node set.  Overridable for the
        same reason as `neighbors_batch`; the default serves any store
        whose `node_features` arrays are locally indexable (in-memory or
        mmap)."""
        ids = np.asarray(ids, np.int64)
        return {k: np.asarray(np.asarray(v)[ids])
                for k, v in self.node_features.get(node_set, {}).items()}


def sample_subgraph(store: GraphStore, spec: SamplingSpec, seed: int,
                    rng: np.random.Generator) -> GraphTensor:
    """Algorithm 1 for a single root: `sample_subgraphs` with one root."""
    return sample_subgraphs(store, spec, [seed], [rng])[0]


def sample_subgraphs(store: GraphStore, spec: SamplingSpec,
                     roots: Sequence[int],
                     rngs: Sequence[np.random.Generator]
                     ) -> list[GraphTensor]:
    """Algorithm 1 for many roots at once, root ``roots[i]`` drawing from
    ``rngs[i]``.

    Each subgraph, and the state its generator is left in, is what
    sampling that root alone gives: per op, the frontier is the root's
    sorted unique input nodes; a frontier node keeps all its neighbours
    up to the fanout, else ``rng.choice(neighbours, fanout,
    replace=False)`` (``neighbours[:fanout]`` under TOP_K), drawn node by
    node in frontier order.  Node sets list the root first in the seed
    set, then ascending ids; edge sets hold the sorted unique (source,
    target) pairs, in the order of the op that first gave the root an
    edge there; an edge set the root reached nothing through gets one
    phantom row and size 0.

    The work runs one op at a time for all roots together, over flat
    arrays keyed by (root index, node id): one `neighbors_flat` store
    call per op, every draw of the op replayed from raw generator words
    (`_draw_choices`), and the dedup of nodes and edges, the local ids
    and the feature gathers as sorts over the flat keys."""
    roots = np.asarray(roots, np.int64).reshape(-1)
    if len(rngs) != len(roots):
        raise ValueError(f"{len(roots)} roots but {len(rngs)} generators")
    if len({id(g) for g in rngs}) < len(rngs):
        # a generator shared by several roots draws root after root
        return [sample_subgraphs(store, spec, roots[i:i + 1],
                                 rngs[i:i + 1])[0]
                for i in range(len(roots))]
    n_roots = len(roots)
    _count(roots=n_roots)
    schema, num_nodes = store.schema, store.num_nodes
    ops = spec.sampling_ops
    _check_ids(roots, num_nodes[spec.seed_node_set], spec.seed_node_set)

    # ---- frontier expansion, one op for every root -------------------------
    # op name -> sorted unique (root index, node id) of the nodes it sampled
    op_keys = {spec.seed_op_name: (np.arange(n_roots), roots)}
    inputs = {name for op in ops for name in op.input_op_names}
    edge_parts: dict[str, list] = {}   # edge set -> [(root, u, v)] per op
    first_op: dict[str, np.ndarray] = {}  # edge set -> per root, the first
    #                                       op that gave it an edge there
    for o, op in enumerate(ops):
        es = schema.edge_sets[op.edge_set_name]
        if len(op.input_op_names) == 1:    # sorted unique already
            fr_root, fr_node = op_keys[op.input_op_names[0]]
        else:
            (fr_root, fr_node), _ = _unique_rows(
                [_cat([op_keys[n][c] for n in op.input_op_names])
                 for c in (0, 1)], (n_roots, num_nodes[es.source]))
        counts, targets = store.neighbors_flat(op.edge_set_name, fr_node)
        k = op.sample_size
        take = np.minimum(counts, k)
        row = np.repeat(np.arange(len(fr_node)), take)
        first = np.cumsum(take) - take        # each frontier node's picks
        starts = np.cumsum(counts) - counts   # its neighbours in `targets`
        pos = np.repeat(starts - first, take) + np.arange(len(row))
        if op.strategy == RANDOM_UNIFORM:
            big = np.flatnonzero(counts > k)
            if len(big) and k:
                picks = _draw_choices(rngs, fr_root[big], counts[big], k)
                pos[first[big, None] + np.arange(k)] = (starts[big, None]
                                                       + picks)
        _check_ids(targets, num_nodes[es.target], es.target)
        r, u, v = fr_root[row], fr_node[row], targets[pos]
        if op.op_name in inputs:
            op_keys[op.op_name], _ = _unique_rows(
                (r, v), (n_roots, num_nodes[es.target]))
        edge_parts.setdefault(op.edge_set_name, []).append((r, u, v))
        got = first_op.setdefault(op.edge_set_name,
                                  np.full(n_roots, len(ops)))
        reached = np.bincount(fr_root[counts > 0], minlength=n_roots) > 0
        got[reached & (got == len(ops))] = o

    # ---- dedup of edges and nodes, local ids -------------------------------
    ns_names = list(dict.fromkeys(
        [spec.seed_node_set] + [name for op in ops for name in (
            schema.edge_sets[op.edge_set_name].source,
            schema.edge_sets[op.edge_set_name].target)]))
    # node set -> [(label, root, node id)] of every place a node is named
    members: dict[str, list] = {ns: [] for ns in ns_names}
    members[spec.seed_node_set].append((None, np.arange(n_roots), roots))
    edge_sizes: dict[str, list] = {}
    for es_name, parts in edge_parts.items():
        es = schema.edge_sets[es_name]
        (r, u, v), _ = _unique_rows(
            [_cat([p[c] for p in parts]) for c in range(3)],
            (n_roots, num_nodes[es.source], num_nodes[es.target]))
        edge_sizes[es_name] = np.bincount(r, minlength=n_roots).tolist()
        members[es.source].append(((es_name, 0), r, u))
        members[es.target].append(((es_name, 1), r, v))

    local_ids: dict = {}                   # label -> local ids, per member
    node_sizes: dict[str, list] = {}
    node_feats: dict[str, dict] = {}
    for ns in ns_names:
        parts = members[ns]
        (ur, uid), inv = _unique_rows(
            [_cat([p[1] for p in parts]), _cat([p[2] for p in parts])],
            (n_roots, num_nodes[ns]), inverse=True)
        sizes = np.bincount(ur, minlength=n_roots)
        offsets = np.cumsum(sizes) - sizes
        local = np.arange(len(uid)) - offsets[ur]
        ordered = uid
        if ns == spec.seed_node_set:      # the root first, then ascending
            root = roots[ur]
            local = np.where(uid == root, 0, local + (uid < root))
            ordered = np.empty_like(uid)
            ordered[offsets[ur] + local] = uid
        at = 0
        for label, r, _ in parts:
            local_ids[label] = local[inv[at:at + len(r)]]
            at += len(r)
        node_sizes[ns] = sizes.tolist()
        node_feats[ns] = {
            f: _split(a, node_sizes[ns])
            for f, a in store.gather_node_features(ns, ordered).items()}

    adjacency = {}
    for es_name, sizes in edge_sizes.items():
        adjacency[es_name] = [
            _split(local_ids[(es_name, end)].astype(np.int32), sizes)
            for end in (0, 1)]

    # ---- one GraphTensor per root ------------------------------------------
    first_op = {e: got.tolist() for e, got in first_op.items()}
    graphs = []
    for i in range(n_roots):
        node_sets = {
            ns: NodeSet(np.asarray([node_sizes[ns][i]], np.int32),
                        {f: a[i] for f, a in node_feats[ns].items()},
                        node_sizes[ns][i])
            for ns in ns_names}
        edge_sets = {}
        for es_name in sorted((e for e in edge_sizes
                               if first_op[e][i] < len(ops)),
                              key=lambda e: first_op[e][i]):
            es = schema.edge_sets[es_name]
            src, tgt = adjacency[es_name]
            m = edge_sizes[es_name][i]
            edge_sets[es_name] = EdgeSet(
                np.asarray([m], np.int32),
                Adjacency(src[i], tgt[i], es.source, es.target), {},
                m) if m else _empty_edge_set(es)
        # every other schema edge set between sampled node sets, empty
        for es_name, es in schema.edge_sets.items():
            if es_name not in edge_sets and es.source in node_sets \
                    and es.target in node_sets:
                edge_sets[es_name] = _empty_edge_set(es)
        graphs.append(GraphTensor(
            Context(np.asarray([1], np.int32), {}), node_sets, edge_sets))
    return graphs


def _empty_edge_set(es) -> EdgeSet:
    return EdgeSet(np.asarray([0], np.int32),
                   Adjacency(np.zeros(1, np.int32), np.zeros(1, np.int32),
                             es.source, es.target), {}, 1)


def _check_ids(ids: np.ndarray, n: int, node_set: str) -> None:
    # keys pack ids by the node count: an id past it would alias another
    if len(ids) and (ids.min() < 0 or ids.max() >= n):
        raise ValueError(f"{node_set} id out of range [0, {n})")


def _split(a: np.ndarray, sizes: list[int]) -> list[np.ndarray]:
    ends = itertools.accumulate(sizes)
    return [a[e - n:e] for e, n in zip(ends, sizes)]


def _cat(arrays: Sequence[np.ndarray]) -> np.ndarray:
    if len(arrays) == 1:
        return np.asarray(arrays[0], np.int64)
    return (np.concatenate(arrays).astype(np.int64, copy=False) if arrays
            else np.zeros(0, np.int64))


# A key packed from several id columns must stay below this; a wider key
# space sorts with `np.lexsort` instead.
_KEY_LIMIT = 2 ** 63


def _unique_rows(cols: Sequence[np.ndarray], bounds: Sequence[int], *,
                 inverse: bool = False
                 ) -> tuple[list[np.ndarray], Optional[np.ndarray]]:
    """The sorted unique rows of integer columns, column c in
    ``[0, bounds[c])``, as columns; with `inverse`, also each input row's
    index among them.  Rows pack into one int64 key (mixed radix over
    `bounds`) where the key space fits, else sort with `np.lexsort`."""
    cols = [np.asarray(c, np.int64) for c in cols]
    if math.prod(bounds) <= _KEY_LIMIT:
        key = cols[0]
        for c, b in zip(cols[1:], bounds[1:]):
            key = key * b + c
        if inverse:
            key, inv = np.unique(key, return_inverse=True)
        else:
            key, inv = np.unique(key), None
        out = []
        for b in bounds[:0:-1]:
            key, rest = np.divmod(key, b)
            out.append(rest)
        return [key] + out[::-1], inv
    order = np.lexsort(cols[::-1])
    cols = [c[order] for c in cols]
    new = np.ones(len(order), bool)
    if len(order):
        new[1:] = np.any([c[1:] != c[:-1] for c in cols], axis=0)
    inv = None
    if inverse:
        inv = np.empty(len(order), np.int64)
        inv[order] = np.cumsum(new) - 1
    return [c[new] for c in cols], inv


# ---------------------------------------------------------------------------
# Draws: `Generator.choice` replayed from raw words
# ---------------------------------------------------------------------------

# `Generator.choice(n, k, replace=False)` runs Floyd's algorithm for
# populations up to this (NumPy 2); past it `choice` may shuffle a tail
# instead, so such draws go to `choice` itself.
_FLOYD_MAX_POPULATION = 10_000

_COUNT_LOCK = threading.Lock()
_COUNTS = {"roots": 0, "draws_replayed": 0, "root_ops_redone": 0}


def sampling_counts() -> dict[str, int]:
    """Process-wide counts since import: roots sampled, ``rng.choice``
    draws replayed from raw words, and (root, op) pairs whose draws were
    redone with ``rng.choice`` (a rejected word, a population past
    Floyd's range, or a bit generator other than PCG64).  The redone
    share is the replay's miss rate."""
    with _COUNT_LOCK:
        return dict(_COUNTS)


def _count(**deltas: int) -> None:
    with _COUNT_LOCK:
        for name, n in deltas.items():
            _COUNTS[name] += int(n)


def _draw_words(bit_generator, state: dict, count: int
                ) -> tuple[np.ndarray, dict]:
    """The next `count` 32-bit words that PCG64's bounded draws read from
    `bit_generator`, now in `state`, with one ``random_raw`` call, and
    the ``has_uint32``/``uinteger`` those draws leave.  A held half word
    comes first; then each 64-bit output gives its low half, and holds
    its high half for the next draw, which clears the flag but not the
    value."""
    held = 1 if state["has_uint32"] else 0
    raw = bit_generator.random_raw((count - held + 1) // 2)
    words = raw.astype("<u8", copy=False).view("<u4")   # low, high, ...
    if held:
        words = np.concatenate([[np.uint32(state["uinteger"])], words])
    return words[:count], {
        "has_uint32": int(len(words) > count),
        "uinteger": int(raw[-1] >> 32) if len(raw) else state["uinteger"]}


def _replay_choice(words: np.ndarray, pops: np.ndarray, k: int
                   ) -> tuple[np.ndarray, np.ndarray]:
    """NumPy 2's ``Generator.choice(n, k, replace=False)`` for n <= 10,000,
    one row per call, from the 32-bit words it reads: `words` [m, 2k - 1]
    (uint64), `pops` [m] with each n > k.

    Floyd's algorithm draws v in [0, j] for j = n-k .. n-1 and takes j
    where v was taken already; a Fisher-Yates pass then swaps position i
    with a draw in [0, i] for i = k-1 .. 1.  Each draw is Lemire's
    bounded method on one word: the high half of word * (bound + 1), the
    word rejected where the low half falls below 2**32 mod (bound + 1).
    Returns the picks [m, k] (indices into each population) and, per
    row, whether a word was rejected: the call then read more words, and
    that row's picks are void."""
    m = len(pops)
    rejected = np.zeros(m, bool)

    def draw(w, top):                     # Lemire's method, in [0, top]
        excl = np.asarray(top, np.uint64) + np.uint64(1)
        prod = w * excl
        rejected[...] |= (prod & np.uint64(0xFFFFFFFF)) < (
            np.uint64(1 << 32) % excl)
        return (prod >> np.uint64(32)).astype(np.int64)

    picks = np.empty((m, k), np.int64)
    pops = np.asarray(pops, np.int64)
    for c in range(k):                    # Floyd
        j = pops - k + c
        v = draw(words[:, c], j)
        taken = (picks[:, :c] == v[:, None]).any(axis=1)
        picks[:, c] = np.where(taken, j, v)
    rows = np.arange(m)
    for t, i in enumerate(range(k - 1, 0, -1)):   # Fisher-Yates
        s = draw(words[:, k + t], i)
        held = picks[rows, s]
        picks[rows, s] = picks[:, i]
        picks[:, i] = held
    return picks, rejected


def _draw_choices(rngs: Sequence[np.random.Generator], row_rng: np.ndarray,
                  pops: np.ndarray, k: int) -> np.ndarray:
    """``rngs[row_rng[i]].choice(pops[i], k, replace=False)`` for every
    row i, each generator's rows called in order (they are contiguous),
    and each generator left where those calls leave it.  Every call of a
    PCG64 generator is replayed at once from its raw words; a generator
    whose rows meet a rejected word is reset and calls ``choice``, as do
    other bit generators and populations past Floyd's range.  Returns
    [rows, k] indices into each population."""
    out = np.empty((len(pops), k), np.int64)
    cuts = np.flatnonzero(np.diff(row_rng)) + 1
    words, replayed, redone, n_replayed = [], [], 0, 0
    for lo, hi in zip([0, *cuts], [*cuts, len(pops)]):
        rng = rngs[int(row_rng[lo])]
        bg = rng.bit_generator
        if type(bg) is not np.random.PCG64 \
                or pops[lo:hi].max() > _FLOYD_MAX_POPULATION:
            out[lo:hi] = [rng.choice(n, k, replace=False)
                          for n in pops[lo:hi]]
            redone += 1
            continue
        before = bg.state
        w, after = _draw_words(bg, before, (hi - lo) * (2 * k - 1))
        words.append(w)
        replayed.append((lo, hi, rng, before, after))
    if replayed:
        rows = np.concatenate([np.arange(lo, hi)
                               for lo, hi, *_ in replayed])
        picks, rejected = _replay_choice(
            np.concatenate(words).astype(np.uint64).reshape(
                len(rows), 2 * k - 1), pops[rows], k)
        out[rows] = picks
        at = 0
        for lo, hi, rng, before, after in replayed:
            bg = rng.bit_generator
            void = rejected[at:at + hi - lo].any()
            at += hi - lo
            if void:
                bg.state = before
                out[lo:hi] = [rng.choice(n, k, replace=False)
                              for n in pops[lo:hi]]
                redone += 1
                continue
            n_replayed += hi - lo
            if any(before[key] != after[key] for key in after):
                bg.state = {**bg.state, **after}
    _count(draws_replayed=n_replayed, root_ops_redone=redone)
    return out


def seed_rng(base_seed: int, root: int) -> np.random.Generator:
    """The repo-wide deterministic sampling convention: every rooted
    subgraph is drawn from its OWN generator keyed on (base_seed, root).

    This makes sampled output a pure function of the root — independent of
    which worker/shard draws it, in what order, or how many there are —
    which is what lets `distributed_sample` re-run a failed shard
    idempotently and lets the async sampler fleet
    (`repro.sampling_service`) reproduce the in-process stream exactly."""
    return np.random.default_rng((base_seed, int(root)))


class InMemorySampler:
    """Medium-scale path (§6.1.2): samples on demand, nothing persisted.
    Per-root generators (see `seed_rng`): ``sample([a, b]) ==
    sample([b, a])`` element-wise, and equals what `distributed_sample`
    persists for the same roots and base seed."""

    def __init__(self, store: GraphStore, spec: SamplingSpec, *,
                 seed: int = 0,
                 rng_factory: Callable[[int], np.random.Generator]
                 | None = None):
        """`rng_factory(root) -> Generator` overrides the default
        `seed_rng(seed, root)` derivation — the injection point for
        callers that manage their own seed tree.  The factory must stay
        a pure function of the root or the per-root determinism contract
        above is lost."""
        self.store = store
        self.spec = spec
        self.seed = seed
        self._rng_factory = rng_factory or (
            lambda root: seed_rng(self.seed, root))

    def sample(self, roots: Sequence[int]) -> list[GraphTensor]:
        roots = [int(r) for r in roots]
        return sample_subgraphs(self.store, self.spec, roots,
                                [self._rng_factory(r) for r in roots])


def shard_partition(seeds: Sequence[int], num_shards: int
                    ) -> list[np.ndarray]:
    """The sampler's shard striping (``seeds[s::num_shards]``) — the
    single owner of how `distributed_sample` partitions roots into shard
    files, so consumers that need the file-order root list (e.g. to feed
    the same roots to the sampling service) derive it from here instead
    of re-implementing the stride."""
    seeds = np.asarray(seeds)
    return [seeds[shard::num_shards] for shard in range(num_shards)]


def distributed_sample(store: GraphStore, spec: SamplingSpec,
                       seeds: Sequence[int], out_dir: str, *,
                       num_shards: int = 4, base_seed: int = 0,
                       writer: Callable | None = None) -> list[str]:
    """Large-scale path (§6.1.1): shard the seeds, run Algorithm 1 per
    shard, persist one file per shard (the fault-tolerance unit — a failed
    shard is simply re-run; output write is atomic via tmp+rename).

    Deterministic regardless of `num_shards`: each root draws from
    `seed_rng(base_seed, root)`, so the union of sampled subgraphs over
    all shards is a pure function of (seeds, base_seed) — only the
    grouping into files depends on the shard count."""
    from repro.data.serialization import save_graphs
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for shard, shard_seeds in enumerate(shard_partition(seeds, num_shards)):
        graphs = sample_subgraphs(
            store, spec, shard_seeds,
            [seed_rng(base_seed, int(s)) for s in shard_seeds])
        path = os.path.join(out_dir, f"samples-{shard:05d}-of-"
                                     f"{num_shards:05d}.npz")
        tmp = path + ".tmp"
        (writer or save_graphs)(graphs, tmp)
        os.replace(tmp, path)
        paths.append(path)
    return paths
