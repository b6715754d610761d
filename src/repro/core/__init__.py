"""repro.core — the TF-GNN data model + modeling API in JAX.

API levels (paper Fig. 1):
  L1 data:      GraphSchema, GraphTensor (+ repro.data batching/padding)
  L2 exchange:  broadcast_*/pool_*/segment_softmax (repro.core.ops)
  L3 modeling:  Conv classes, GraphUpdate, model zoo
  L4 orchestration: repro.orchestration.runner

Import-laziness contract (enforced by tools/repro_lint rule PUR005):
importing this package — which happens whenever ANY ``repro.core.*``
submodule is imported — must not drag in jax, because the numpy-only
sampler workers load the L1 data model (`graph_tensor`, `schema`)
through here.  The convenience re-exports below therefore resolve
lazily via PEP 562 module ``__getattr__``: ``from repro.core import
GATv2Conv`` still works everywhere, but only pulls the jax-heavy L2/L3
modules when actually used.
"""
from importlib import import_module

# name -> defining submodule; "" marks the submodule itself as the export
_EXPORTS = {
    # L1 data model (jax-free by contract)
    "Adjacency": "graph_tensor", "Context": "graph_tensor",
    "EdgeSet": "graph_tensor", "GraphTensor": "graph_tensor",
    "NodeSet": "graph_tensor", "CONTEXT": "graph_tensor",
    "HIDDEN_STATE": "graph_tensor", "SOURCE": "graph_tensor",
    "TARGET": "graph_tensor",
    "FeatureSpec": "schema", "GraphSchema": "schema",
    "NodeSetSpec": "schema", "EdgeSetSpec": "schema",
    "mag_schema": "schema", "recsys_schema": "schema",
    # L2 exchange ops (jax)
    "ops": "",
    "broadcast_node_to_edges": "ops", "pool_edges_to_node": "ops",
    "broadcast_context_to_nodes": "ops",
    "broadcast_context_to_edges": "ops",
    "pool_nodes_to_context": "ops", "pool_edges_to_context": "ops",
    "segment_softmax": "ops", "node_degree": "ops", "use_kernels": "ops",
    # L3 modeling (jax)
    "AnyToAnyConv": "convolutions", "GATv2Conv": "convolutions",
    "GCNConv": "convolutions", "MultiHeadAttentionConv": "convolutions",
    "SAGEConv": "convolutions", "SimpleConv": "convolutions",
    "ContextUpdate": "graph_update", "EdgeSetUpdate": "graph_update",
    "GraphUpdate": "graph_update", "HGTUpdate": "graph_update",
    "MapFeatures": "graph_update",
    "NextStateFromConcat": "graph_update",
    "NodeSetUpdate": "graph_update", "ResidualNextState": "graph_update",
    "SingleInputNextState": "graph_update",
    "models": "",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        submodule = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    if submodule == "":
        value = import_module(f"{__name__}.{name}")
    else:
        value = getattr(import_module(f"{__name__}.{submodule}"), name)
    globals()[name] = value  # cache: __getattr__ runs once per name
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
