"""The graph a configuration trains and serves on, and its on-disk cache.

The graph is the dataset: it is drawn from a fixed dataset seed, like a
downloaded OGBN-MAG, and never from a run's ``--seed``.  Building it
takes tens of seconds of host work (the generator loops over every
paper), so the first run in a checkout writes it under ``bench/.cache/``
(git-ignored) and later runs load it.  The cache key is a hash of the
generator's name and every parameter, the dataset seed among them, so a
changed configuration never reads a stale graph.

A pre-sampled pool of rooted subgraphs (TF-GNN's offline sampling, paper
§6) is cached the same way, keyed by the graph's key, the sampling spec,
the pool's roots and its sampling seed.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np

CACHE_DIR = Path(__file__).resolve().parents[1] / ".cache"


def cache_key(obj) -> str:
    """A short stable hash of a JSON-serialisable description."""
    text = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()[:20]


def _atomic_savez(path: Path, arrays: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp.npz")
    np.savez(tmp, **arrays)
    os.replace(tmp, path)


def build_store(dataset: dict):
    """Runs the generator that ``dataset["generator"]`` names with the
    configuration's parameters and the dataset seed."""
    from repro.data import synthetic
    gen = getattr(synthetic, dataset["generator"])
    store, _ = gen(seed=int(dataset["seed"]), **dataset["params"])
    return store


def _store_arrays(store) -> dict:
    out = {}
    for name, (src, tgt) in store.edges.items():
        out[f"edges/{name}/src"] = src
        out[f"edges/{name}/tgt"] = tgt
    for ns, feats in store.node_features.items():
        for k, v in feats.items():
            out[f"nodes/{ns}/{k}"] = np.asarray(v)
    for ns, n in store.num_nodes.items():
        out[f"num_nodes/{ns}"] = np.asarray(n)
    return out


def _store_from_arrays(arrays, schema):
    from repro.data.sampling import GraphStore
    edges, feats, num = {}, {}, {}
    for key in arrays.files:
        kind, name, *leaf = key.split("/")
        leaf = leaf[0] if leaf else None
        if kind == "edges":
            edges.setdefault(name, [None, None])[leaf == "tgt"] = arrays[key]
        elif kind == "nodes":
            feats.setdefault(name, {})[leaf] = arrays[key]
        else:
            num[name] = int(arrays[key])
    return GraphStore(schema, {k: tuple(v) for k, v in edges.items()},
                      feats, num)


def load_store(dataset: dict, cache_dir: Path = CACHE_DIR):
    """(store, built): the cached graph for `dataset`, built and written
    first when the cache has none."""
    from repro.core.schema import mag_schema
    path = Path(cache_dir) / f"graph-{cache_key(dataset)}.npz"
    if path.exists():
        with np.load(path) as arrays:
            return _store_from_arrays(arrays, mag_schema()), False
    store = build_store(dataset)
    _atomic_savez(path, _store_arrays(store))
    return store, True


# ---------------------------------------------------------------------------
# Pre-sampled pools
# ---------------------------------------------------------------------------

def _pack_graphs(graphs) -> dict:
    """Unpadded one-component GraphTensors -> flat arrays: every leaf
    concatenated along its first axis, with per-graph lengths."""
    g0 = graphs[0]
    meta = {"nodes": {n: sorted(ns.features) for n, ns in
                      g0.node_sets.items()},
            "edges": {n: [es.adjacency.source_name, es.adjacency.target_name]
                      for n, es in g0.edge_sets.items()}}
    out = {"meta": np.asarray(json.dumps(meta))}
    for n, keys in meta["nodes"].items():
        out[f"nodes/{n}/#n"] = np.asarray(
            [g.node_sets[n].capacity for g in graphs], np.int64)
        for k in keys:
            out[f"nodes/{n}/{k}"] = np.concatenate(
                [np.asarray(g.node_sets[n].features[k]) for g in graphs])
    for n in meta["edges"]:
        out[f"edges/{n}/#n"] = np.asarray(
            [g.edge_sets[n].capacity for g in graphs], np.int64)
        out[f"edges/{n}/#size"] = np.asarray(
            [int(np.asarray(g.edge_sets[n].sizes).sum()) for g in graphs],
            np.int64)
        out[f"edges/{n}/#src"] = np.concatenate(
            [np.asarray(g.edge_sets[n].adjacency.source) for g in graphs])
        out[f"edges/{n}/#tgt"] = np.concatenate(
            [np.asarray(g.edge_sets[n].adjacency.target) for g in graphs])
    return out


def _unpack_graphs(arrays) -> list:
    from repro.core.graph_tensor import (Adjacency, Context, EdgeSet,
                                         GraphTensor, NodeSet)
    meta = json.loads(str(arrays["meta"]))
    count = len(arrays[f"nodes/{next(iter(meta['nodes']))}/#n"])

    def slices(key):
        n = arrays[key]
        bounds = np.concatenate([[0], np.cumsum(n)])
        return [slice(int(a), int(b)) for a, b in zip(bounds[:-1],
                                                      bounds[1:])]

    node_cols = {n: (slices(f"nodes/{n}/#n"),
                     {k: arrays[f"nodes/{n}/{k}"] for k in keys})
                 for n, keys in meta["nodes"].items()}
    edge_cols = {n: (slices(f"edges/{n}/#n"), arrays[f"edges/{n}/#size"],
                     arrays[f"edges/{n}/#src"], arrays[f"edges/{n}/#tgt"])
                 for n in meta["edges"]}
    graphs = []
    one = np.asarray([1], np.int32)
    for i in range(count):
        node_sets = {}
        for n, (sl, cols) in node_cols.items():
            s = sl[i]
            node_sets[n] = NodeSet(np.asarray([s.stop - s.start], np.int32),
                                   {k: v[s] for k, v in cols.items()},
                                   s.stop - s.start)
        edge_sets = {}
        for n, (sl, size, src, tgt) in edge_cols.items():
            s = sl[i]
            src_name, tgt_name = meta["edges"][n]
            edge_sets[n] = EdgeSet(
                np.asarray([size[i]], np.int32),
                Adjacency(src[s], tgt[s], src_name, tgt_name), {},
                s.stop - s.start)
        graphs.append(GraphTensor(Context(one, {}), node_sets, edge_sets))
    return graphs


def load_pool(store, spec, roots, sample_seed: int, graph_key: str,
              cache_dir: Path = CACHE_DIR):
    """(graphs, built): the subgraphs Algorithm 1 draws for `roots`, each
    from `seed_rng(sample_seed, root)`; read from the cache when present."""
    from repro.data.sampling import sample_subgraph, seed_rng
    desc = {"graph": graph_key, "spec": repr(spec),
            "roots": cache_key(np.asarray(roots).tolist()),
            "seed": int(sample_seed)}
    path = Path(cache_dir) / f"pool-{cache_key(desc)}.npz"
    if path.exists():
        with np.load(path) as arrays:
            return _unpack_graphs(arrays), False
    graphs = [sample_subgraph(store, spec, int(r), seed_rng(sample_seed,
                                                            int(r)))
              for r in roots]
    _atomic_savez(path, _pack_graphs(graphs))
    return graphs, True
