"""Low-latency GNN inference serving, end to end (§6 online regime).

Stands up a `GNNServer` over synthetic MAG — on-demand seeded subgraph
sampling, dynamic micro-batching into a warmed bucket ladder, versioned
subgraph + node-embedding caches — then drives it the three ways the
benchmark gates: synchronous queries, a closed-loop client fleet, and an
open-loop (seeded-Poisson) arrival schedule.  Finishes with the
freshness story: mutating the graph bumps the store version, stale cache
entries are evicted, and re-served queries resample.

Exits non-zero if any steady-state request triggered an XLA compile —
the serving invariant (`make smoke-serve` runs this under 8 forced CPU
devices):

    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        PYTHONPATH=src python examples/gnn_serve.py
"""
import argparse
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--papers", type=int, default=600)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests-per-client", type=int, default=25)
    ap.add_argument("--open-loop-s", type=float, default=1.0)
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args(argv)

    import jax
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    from repro.core import HIDDEN_STATE, mag_schema
    from repro.core.models import vanilla_mpnn
    from repro.data import SamplingSpecBuilder
    from repro.data.synthetic import synthetic_mag
    from repro.nn.layers import Linear
    from repro.nn.module import split_params
    from repro.orchestration import RootNodeMulticlassClassification
    from repro.serve import (GNNServer, VersionedGraphStore, closed_loop,
                             open_loop, spec_size_bounds)

    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")

    # -- graph + sampling spec: 2-hop citation neighbourhoods -----------
    dim, n_classes = 32, 8
    raw, _ = synthetic_mag(n_papers=args.papers,
                           n_authors=args.papers // 2,
                           n_institutions=20, n_fields=40,
                           n_classes=n_classes, feat_dim=32)
    store = VersionedGraphStore.wrap(raw)
    schema = mag_schema()
    b = SamplingSpecBuilder(schema)
    seed_op = b.seed("paper")
    seed_op.sample(8, "cites").sample(4, "cites")
    spec = seed_op.build()
    bounds = spec_size_bounds(spec, schema)
    print(f"per-request worst case: {bounds.total_num_nodes} nodes, "
          f"{bounds.total_num_edges} edges")

    # -- model: init states -> 2-round MPNN -> root-node head -----------
    init = Linear(32, dim)
    gnn = vanilla_mpnn({"cites": ("paper", "paper")}, {"paper": dim},
                       message_dim=dim, hidden_dim=dim, num_rounds=2)
    task = RootNodeMulticlassClassification("paper", n_classes, dim)
    head = task.head()
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"init": split_params(init.init(k1))[0],
              "gnn": split_params(gnn.init(k2))[0],
              "head": split_params(head.init(k3))[0]}

    def apply_fn(p, graph):
        g = graph.replace_features(node_sets={
            "paper": {HIDDEN_STATE: jax.nn.relu(
                init(p["init"], graph.node_sets["paper"]["feat"]))}})
        g = gnn(p["gnn"], g)
        return task.predict(p["head"], g)

    # -- serve ----------------------------------------------------------
    t0 = time.perf_counter()
    server = GNNServer(store, spec, apply_fn, params, feature_dim=dim,
                       max_batch=args.max_batch, batch_window_ms=1.0)
    print(f"warmup: {time.perf_counter() - t0:.2f}s, bucket ladder "
          f"{list(server.ladder.rungs)}"
          + (" (top rung trimmed by kernel VMEM budget)"
             if server.ladder.budget_limited else ""))
    try:
        logits = server.serve_sync([1, 2, 3], timeout=30)
        print(f"serve_sync([1, 2, 3]) -> logits {logits.shape}, "
              f"argmax {np.argmax(logits, axis=-1).tolist()}")

        roots = range(min(args.papers, 400))
        rep = closed_loop(server, roots, clients=args.clients,
                          requests_per_client=args.requests_per_client,
                          seed=0)
        print(f"closed loop: {rep.summary()}")
        rep2 = open_loop(server, roots, qps=max(rep.qps * 0.5, 20.0),
                         duration_s=args.open_loop_s, seed=1)
        print(f"open loop:   {rep2.summary()}")

        # -- freshness: mutate the graph, caches invalidate -------------
        before = server.submit(5).result(30)
        assert np.allclose(before, server.submit(5).result(30))
        v0 = store.version
        store.add_edges("cites", [5], [int(args.papers - 1)])
        assert store.version == v0 + 1, "mutation must bump the version"
        server.submit(5).result(30)  # resamples: stale entries evicted
        stats = server.stats
        assert stats.invalidations > 0, "stale entries were not evicted"
        print(f"freshness: version {v0} -> {store.version}, "
              f"{stats.invalidations} stale entries evicted")

        recompiles = server.steady_state_recompiles
        print(f"stats: {stats.served} served in {stats.batches} batches "
              f"{dict(sorted(stats.batch_sizes.items()))}, "
              f"embedding hits/misses "
              f"{stats.embedding_hits}/{stats.embedding_misses}, "
              f"steady-state recompiles {recompiles}")
        if rep.errors or rep2.errors:
            raise SystemExit(f"load generation saw errors: "
                             f"closed={rep.errors} open={rep2.errors}")
        if recompiles != 0:
            raise SystemExit(f"serving invariant violated: {recompiles} "
                             "steady-state recompile(s) — a live request "
                             "missed the warmed bucket ladder")
    finally:
        server.close()
    print("OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
