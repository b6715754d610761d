"""The system under test, wired from a configuration file.

Everything here calls the program (`repro`) and nothing here computes a
reference number.  The pieces are the set-up of ``chip_smoke.py``,
copied so that a later change to that script cannot move the yardstick:
`InitStates` (paper features through a linear map, one embedding table
per featureless node set), the sampling spec, the root-classification
task, and weights drawn from ``--seed`` in one jitted call.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import HIDDEN_STATE
from repro.data import SamplingSpecBuilder
from repro.nn.layers import Embedding, Linear
from repro.nn.module import Module, Param
from repro.orchestration import RootNodeMulticlassClassification

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path):
    """Imports a file of the benchmark by path (metric and driver names
    carry dots, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(cfg: dict):
    """bench/models/<kind>.py: the program's GNN for a model kind."""
    return load_module(BENCH / "models" / f"{cfg['model']['kind']}.py")


def sampling_spec(schema, ops: list):
    """A `SamplingSpec` from the configuration's list of sampling ops."""
    b = SamplingSpecBuilder(schema)
    handles = {"seed": b.seed("paper")}
    for op in ops:
        ins = [handles[n] for n in op["inputs"]]
        src = ins[0] if len(ins) == 1 else ins[0].join(ins[1:])
        handles[op["name"]] = src.sample(int(op["sample_size"]),
                                         op["edge_set"])
    return handles["seed"].build()


class InitStates(Module):
    """Paper features -> hidden states; an embedding table per
    featureless node set, one row per node."""

    def __init__(self, feat_dim: int, dim: int, rows: dict):
        self.paper = Linear(feat_dim, dim)
        self.tables = {n: Embedding(r, dim) for n, r in sorted(rows.items())}

    def init(self, key):
        keys = jax.random.split(key, len(self.tables) + 1)
        p = {"paper": self.paper.init(keys[0])}
        for k, (n, t) in zip(keys[1:], self.tables.items()):
            p[n] = t.init(k)
        return p

    def __call__(self, params, graph):
        ns = {"paper": {HIDDEN_STATE: jax.nn.relu(self.paper(
            params["paper"], graph.node_sets["paper"]["feat"]))}}
        for n, t in self.tables.items():
            ns[n] = {HIDDEN_STATE: t(params[n], graph.node_sets[n]["id"],
                                     dtype=jnp.float32)}
        return graph.replace_features(node_sets=ns)


class Preset(Module):
    """A module whose `init` returns weights made beforehand, so that the
    Trainer starts from the benchmark's weights and not its own draw."""

    def __init__(self, inner: Module, values):
        self.inner = inner
        self.values = values

    def init(self, key):
        del key
        return jax.tree_util.tree_map(Param, self.values)

    def __call__(self, params, *args):
        return self.inner(params, *args)


class PresetTask(RootNodeMulticlassClassification):
    """The root-classification task with a head preset the same way."""

    def __init__(self, head_values, **kw):
        super().__init__(**kw)
        self.head_values = head_values

    def head(self) -> Module:
        return Preset(super().head(), self.head_values)


class Program:
    """One configuration's model, task and sampling spec over a store.

    Building one sets the configuration's matmul precision for the whole
    process (every thread, so the serving engine's too): float32 matmuls
    on a TPU otherwise round their operands to bfloat16."""

    def __init__(self, cfg: dict, store):
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
        self.cfg = cfg
        self.store = store
        self.spec = sampling_spec(store.schema, cfg["sampling"])
        m = cfg["model"]
        self.dim = int(m["embedding_dim"])
        self.init_states = InitStates(
            int(cfg["dataset"]["params"]["feat_dim"]), self.dim,
            {n: store.num_nodes[n] for n in m["embedded_node_sets"]})
        edges = {name: (es.source, es.target)
                 for name, es in store.schema.edge_sets.items()}
        self.gnn = model_module(cfg).program_gnn(
            m, edges, {n: self.dim for n in store.num_nodes})
        t = cfg["task"]
        self.task_kw = dict(node_set_name=t["node_set"],
                            num_classes=int(t["num_classes"]),
                            hidden_dim=int(m["hidden_dim"]),
                            label_feature=t["label_feature"])
        self.task = RootNodeMulticlassClassification(**self.task_kw)

    def param_shapes(self):
        """The program's parameter tree, as shapes (nothing computed)."""
        k = jax.random.PRNGKey(0)
        tree = jax.eval_shape(lambda: {
            "init": self.init_states.init(k), "gnn": self.gnn.init(k),
            "head": self.task.head().init(k)})
        return jax.tree_util.tree_map(lambda p: p.value, tree,
                                      is_leaf=lambda x: isinstance(x, Param))

    def make_weights(self, seed: int):
        """Every weight from `seed`, on the device, in one jitted call."""
        return jax.jit(lambda key: draw_weights(self.param_shapes(), key))(
            seed_key(seed))

    def model_fn(self, weights):
        """What `Trainer.fit` calls: the program's modules, preset."""
        return lambda: (Preset(self.init_states, weights["init"]),
                        Preset(self.gnn, weights["gnn"]))

    def preset_task(self, weights) -> PresetTask:
        return PresetTask(weights["head"], **self.task_kw)

    def apply_fn(self):
        """(params, padded graph) -> root logits: the served forward."""
        init_states, gnn, task = self.init_states, self.gnn, self.task

        def apply_fn(params, graph):
            return task.predict(params["head"],
                                gnn(params["gnn"],
                                    init_states(params["init"], graph)))
        return apply_fn


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def draw_weights(shapes, key):
    """Weights for a tree of shapes, by the leaf's name: ``w`` N(0,
    1/fan_in); ``table``, ``b`` and ``bias`` N(0, 0.02^2); ``scale`` 1 +
    N(0, 0.02^2).  The same recipe as the configuration files state."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    out = []
    for i, (path, s) in enumerate(leaves):
        name = path[-1].key
        z = jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
        if name == "w":
            out.append(z / np.sqrt(s.shape[0]))
        elif name in ("table", "b", "bias"):
            out.append(0.02 * z)
        elif name == "scale":
            out.append(1.0 + 0.02 * z)
        else:
            raise ValueError(f"no weight recipe for leaf {name!r}")
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes),
                                        out)


def real_counts(g, *, padded: bool = True) -> dict:
    """Real nodes and edges per set, and real components (roots), of a
    graph; a padded batch's last component is its padding."""
    cut = slice(None, -1) if padded else slice(None)
    return {"nodes": {n: int(np.asarray(s.sizes)[cut].sum())
                      for n, s in g.node_sets.items()},
            "edges": {n: int(np.asarray(s.sizes)[cut].sum())
                      for n, s in g.edge_sets.items()},
            "components": int(np.asarray(g.context.sizes)[cut].sum())}


def flops_fn(cfg: dict, schema):
    """counts -> forward FLOPs, by the model kind's counter."""
    mod = model_module(cfg)
    edges = {n: (e.source, e.target) for n, e in schema.edge_sets.items()}
    feat = int(cfg["dataset"]["params"]["feat_dim"])
    classes = int(cfg["task"]["num_classes"])
    return lambda counts: mod.forward_flops(cfg["model"], edges, feat,
                                            classes, counts)
