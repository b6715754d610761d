"""What decides `correct`: the sampled inputs checked against the graph,
and the numbers the program produced compared with the plain reference.

Every comparison yields one number and has one limit; `Checks` keeps
them in order and prints them beside their limits.  A cell's limits are
``bench/limits/<cell>.json``; the readings they were set from are in
PERF.md.  A limit of None reads a number without comparing it
(``bench/tools/readings.py`` reads every number so).
"""
from __future__ import annotations

import numpy as np

from bench.harness.program import load_module, BENCH


class Checks:
    def __init__(self, limits: dict):
        self.limits = limits
        self.values: dict = {}

    def add(self, name: str, value: float) -> None:
        self.values[name] = float(value)

    @property
    def correct(self) -> bool:
        return bool(self.values) and all(
            np.isfinite(v) and v <= self.limits[k]
            for k, v in self.values.items() if self.limits[k] is not None)

    def as_dict(self) -> dict:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in self.values.items()}

    def lines(self) -> list:
        return [f"check {k}: {v:.6g} (limit {self.limits[k]:.6g})"
                if self.limits[k] is not None else
                f"reading {k}: {v:.6g} (not compared)"
                for k, v in self.values.items()]


def reference_module(cfg: dict):
    """bench/references/<kind>.py."""
    return load_module(BENCH / "references" / f"{cfg['model']['kind']}.py")


def plain_graph(g) -> dict:
    """A sampled (unpadded, one-component) GraphTensor as plain arrays."""
    nodes = {}
    for ns, s in g.node_sets.items():
        nodes[ns] = {k: np.asarray(v) for k, v in s.features.items()}
        nodes[ns]["n"] = int(np.asarray(s.sizes).sum())
    edges = {}
    for es, e in g.edge_sets.items():
        n = int(np.asarray(e.sizes).sum())
        edges[es] = (np.asarray(e.adjacency.source)[:n],
                     np.asarray(e.adjacency.target)[:n])
    return {"nodes": nodes, "edges": edges}


class StoreIndex:
    """Lookups into the full graph: a paper's id from its feature row,
    and whether an edge exists."""

    def __init__(self, store):
        self.store = store
        feat = np.ascontiguousarray(store.node_features["paper"]["feat"])
        keys = feat[:, :2].copy().view(np.int64)[:, 0]
        self.order = np.argsort(keys, kind="stable")
        self.keys = keys[self.order]
        self.codes = {}

    def paper_ids(self, feat: np.ndarray) -> np.ndarray:
        """Global ids of papers by feature row; -1 where no row matches."""
        feat = np.ascontiguousarray(feat, np.float32)
        keys = feat[:, :2].copy().view(np.int64)[:, 0]
        pos = np.clip(np.searchsorted(self.keys, keys), 0,
                      len(self.keys) - 1)
        ids = self.order[pos]
        ok = (self.keys[pos] == keys) & np.all(
            self.store.node_features["paper"]["feat"][ids] == feat, axis=1)
        return np.where(ok, ids, -1)

    def edge_codes(self, es: str) -> np.ndarray:
        if es not in self.codes:
            src, tgt = self.store.edges[es]
            tgt_ns = self.store.schema.edge_sets[es].target
            self.codes[es] = np.unique(src.astype(np.int64)
                                       * self.store.num_nodes[tgt_ns] + tgt)
        return self.codes[es]


def sample_faults(index: StoreIndex, spec, graphs: list,
                  roots: list) -> int:
    """Faults in sampled subgraphs: a root not first among the papers,
    a node or label that is not the graph's, an edge the graph lacks, or
    more edges out of one node than the spec's fanouts allow."""
    store = index.store
    fanout: dict = {}
    for op in spec.sampling_ops:
        fanout[op.edge_set_name] = fanout.get(op.edge_set_name, 0) \
            + op.sample_size
    faults = 0
    for g, root in zip(graphs, roots):
        gid = {}
        for ns, feats in g["nodes"].items():
            if ns == "paper":
                ids = index.paper_ids(feats["feat"])
                faults += int(np.sum(ids < 0))
                faults += int(ids[0] != root)
                labels = store.node_features["paper"]["labels"][ids]
                faults += int(np.sum(labels != feats["labels"]))
            else:
                ids = np.asarray(feats["id"], np.int64)
                faults += int(np.sum((ids < 0)
                                     | (ids >= store.num_nodes[ns])))
            gid[ns] = ids
        for es, (src, tgt) in g["edges"].items():
            if not len(src):
                continue
            spec_es = store.schema.edge_sets[es]
            s, t = gid[spec_es.source][src], gid[spec_es.target][tgt]
            codes = s * store.num_nodes[spec_es.target] + t
            known = index.edge_codes(es)
            pos = np.clip(np.searchsorted(known, codes), 0, len(known) - 1)
            faults += int(np.sum(known[pos] != codes))
            faults += int(np.sum(np.bincount(src) > fanout.get(es, 0)))
    return faults


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a tree of arrays."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def _median(norms) -> float:
    """The median leaf's norm, over the leaves that are not exactly 0
    (the state of a node set that reaches no root gets no gradient)."""
    nonzero = [v for v in norms if v > 0]
    return float(np.median(nonzero)) if nonzero else 0.0


def leaf_gaps(program: dict, reference: dict, keep=None) -> dict:
    """{leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf}."""
    keys = [k for k in reference if keep is None or k in keep]
    median = _median(reference[k] for k in keys)
    return {k: abs(program[k] - reference[k])
            / max(reference[k], median, np.finfo(np.float32).tiny)
            for k in keys}


def worst_leaves(program: dict, reference: dict, keep=None, n=3) -> str:
    """The `n` worst leaves, for the log."""
    gaps = leaf_gaps(program, reference, keep)
    return "; ".join(f"{k} {gaps[k]:.3g} ({program[k]:.4g} vs "
                     f"{reference[k]:.4g})"
                     for k in sorted(gaps, key=gaps.get, reverse=True)[:n])


def direction_gap(program, reference) -> float:
    """|p/|p| - r/|r||: how far apart two arrays point, whatever their
    scales (the program's side may carry the optimizer's constant
    factors)."""
    p = np.asarray(program, np.float64).ravel()
    r = np.asarray(reference, np.float64).ravel()
    return float(np.linalg.norm(p / np.linalg.norm(p)
                                - r / np.linalg.norm(r)))


def moved_leaves(grad_norms: dict, rule: float = 1e-3) -> set:
    """Leaves whose reference gradient is at least `rule` of the median
    leaf's: the rest move under Adam by round-off alone."""
    median = _median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v > 0 and v >= rule * median}
