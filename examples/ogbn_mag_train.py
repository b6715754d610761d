"""End-to-end driver — the paper's §8 OGBN-MAG case study, soup to nuts:

  schema -> SamplingSpecBuilder (Fig. 6) -> distributed sampler (Alg. 1,
  persisted shards) -> GraphBatcher (merge+pad) -> 4-round MPNN (Fig. 7/8)
  -> RootNodeMulticlassClassification -> runner.run with checkpointing.

Uses the synthetic-MAG generator (OGB download unavailable offline); the
planted signal makes neighborhood aggregation necessary, so the experiment
is qualitatively faithful to Table 1.

    PYTHONPATH=src python examples/ogbn_mag_train.py

Data-parallel over N (possibly host-forced) devices — the batch becomes a
super-batch of padded component groups sharded over the mesh's "data"
axis; loss matches the 1-device run on the same seed:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
        python examples/ogbn_mag_train.py --steps 3 --num-devices 8

``--model-parallel M`` folds the mesh to 2-D (data = N/M rows x model = M
columns): node/edge feature dims shard over "model" (all-gathered exactly
at the broadcast/pool boundary of repro.core.ops) and AdamW state is
ZeRO-1-sharded over "data" — same loss again, with per-device optimizer
state shrunk by the data factor:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
        python examples/ogbn_mag_train.py --steps 3 --num-devices 8 \\
        --model-parallel 2

On TPU the loss matches only with fp32 matmuls: at the default
precision an f32 matmul rounds its operands to bf16, so each model
shard's partial cotangent is rounded on its own before the shards are
summed, and the loss drifts by about 1e-3 relative within three steps.

With ``--sampler service`` the training stream comes from the async
sampling service instead (repro.sampling_service): a fleet of sampler
worker processes runs Algorithm 1 + merge + pad off the training host
path and streams padded super-batches over length-prefixed socket frames,
double-buffered onto the mesh.  Same plan, same per-root sampling seeds
=> bit-identical batches => the same loss as the in-process path:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
        python examples/ogbn_mag_train.py --sampler service --num-devices 8

``--multihost N`` crosses the process boundary — a CPU rehearsal of a
multi-host job on one host: the script relaunches itself as N
`jax.distributed` processes (each contributing ``num_devices / N``
forced CPU devices to one GLOBAL mesh), process 0
additionally hosts a `SamplerEndpoint` whose per-rank `SamplingService`
fleets stream every rank's batches over TCP (`RemoteStreamClient` with
reconnect + resume-from-watermark), and the per-process rank shards are
assembled into global super-batches.  Same plan, same seeds, same global
mesh => the same loss as the single-process run of the same size:

    PYTHONPATH=src python examples/ogbn_mag_train.py --steps 3 \\
        --num-devices 4 --multihost 2

Per-rank logs land in ``--multihost-log-dir`` (the CI smoke job uploads
them as artifacts).  On a host with TPU chips ``--multihost`` refuses to
start: every child would try to take every chip, and a chip belongs to
one process.  Ports are OS-assigned; the coordinator address and
the endpoint address travel to the children via environment / a shared
address file, never fixed port numbers.
"""
import argparse
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

ap = argparse.ArgumentParser()
ap.add_argument("--papers", type=int, default=1200)
ap.add_argument("--epochs", type=int, default=4)
ap.add_argument("--hidden", type=int, default=64)
ap.add_argument("--steps", type=int, default=None,
                help="cap total train steps (smoke runs use --steps 3)")
ap.add_argument("--num-devices", type=int, default=1,
                help="total (GLOBAL) mesh devices; >1 needs that many "
                     "devices "
                     "(XLA_FLAGS=--xla_force_host_platform_device_count=N; "
                     "with --multihost N the launcher forces "
                     "num_devices/N per process)")
ap.add_argument("--model-parallel", type=int, default=1,
                help="model columns of the 2-D mesh (must divide "
                     "--num-devices); feature dims shard over 'model', "
                     "optimizer state ZeRO-1-shards over 'data'")
ap.add_argument("--sampler", choices=["inprocess", "service"],
                default="inprocess",
                help="'service' streams training batches from the async "
                     "sampler fleet (identical loss, sampling off the "
                     "trainer host path)")
ap.add_argument("--sampler-workers", type=int, default=2,
                help="sampler fleet size for --sampler service")
ap.add_argument("--multihost", type=int, default=0, metavar="N",
                help="launch N jax.distributed processes sharing one "
                     "global mesh of --num-devices devices; sampler "
                     "batches stream from a rank-0 SamplerEndpoint over "
                     "TCP.  Reaches the same loss as the 1-process run "
                     "of the same --num-devices")
ap.add_argument("--multihost-log-dir", default="",
                help="directory for per-rank log files (default: a temp "
                     "dir, printed at launch)")
ap.add_argument("--multihost-timeout", type=float, default=900.0,
                help="launcher kills the fleet after this many seconds")
args = ap.parse_args()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_multihost(args) -> int:
    """Parent mode: spawn --multihost N child processes of this very
    command line (children are marked by REPRO_PROCESS_ID), harvest
    their per-rank logs, and propagate failure.  Never imports jax."""
    from repro.runtime import tpu_chips_attached
    chips = tpu_chips_attached()
    if chips:
        raise SystemExit(
            f"--multihost is a CPU rehearsal of multi-host training, and "
            f"this host has {chips} TPU chip(s): each of the {args.multihost} "
            f"children would try to take all of them.  Run one process "
            f"per host (--num-devices N uses every local chip).")
    nproc = args.multihost
    if args.num_devices % nproc:
        raise SystemExit(f"--multihost {nproc} must divide "
                         f"--num-devices {args.num_devices}")
    local_dev = args.num_devices // nproc
    coord = f"127.0.0.1:{_free_port()}"
    tmp = tempfile.mkdtemp(prefix="ogbn_multihost_")
    endpoint_file = os.path.join(tmp, "endpoint_addr")
    log_dir = args.multihost_log_dir or os.path.join(tmp, "logs")
    os.makedirs(log_dir, exist_ok=True)
    print(f"multihost: {nproc} processes x {local_dev} devices, "
          f"coordinator {coord}, logs in {log_dir}", flush=True)
    procs = []
    for r in range(nproc):
        env = dict(os.environ,
                   REPRO_COORDINATOR=coord,
                   REPRO_NUM_PROCESSES=str(nproc),
                   REPRO_PROCESS_ID=str(r),
                   REPRO_ENDPOINT_FILE=endpoint_file,
                   XLA_FLAGS="--xla_force_host_platform_device_count="
                             f"{local_dev}")
        log = open(os.path.join(log_dir, f"rank{r}.log"), "wb")
        procs.append((r, subprocess.Popen(
            [sys.executable] + sys.argv, env=env,
            stdout=log, stderr=subprocess.STDOUT), log))
    deadline = time.monotonic() + args.multihost_timeout
    status = 0
    for r, p, log in procs:
        try:
            code = p.wait(max(deadline - time.monotonic(), 0.1))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            code = -9
            print(f"rank {r}: TIMEOUT after "
                  f"{args.multihost_timeout:.0f}s — killed", flush=True)
        log.close()
        if code != 0:
            status = 1
        with open(os.path.join(log_dir, f"rank{r}.log"), "rb") as f:
            tail = f.read()[-2000:].decode(errors="replace")
        print(f"--- rank {r} exit {code}; log tail ---\n{tail}",
              flush=True)
    for _, p, _ in procs:  # a straggler past a peer's failure
        if p.poll() is None:
            p.kill()
            p.wait()
    print("multihost:", "OK" if status == 0 else "FAILED", flush=True)
    return status


if args.multihost > 1 and "REPRO_PROCESS_ID" not in os.environ:
    raise SystemExit(_launch_multihost(args))

import jax

from repro.runtime import enable_compile_cache

enable_compile_cache()

from repro.core import HIDDEN_STATE, mag_schema
from repro.core.models import vanilla_mpnn
from repro.data import (GraphBatcher, SamplingSpecBuilder,
                        distributed_sample, find_size_constraints,
                        load_graphs, shard_partition)
from repro.data.synthetic import synthetic_mag
from repro.distributed.partition import initialize_distributed
from repro.nn.layers import Embedding, Linear
from repro.nn.module import Module
from repro.orchestration import RootNodeMulticlassClassification, run
from repro.sampling_service import (RemoteStreamClient, SamplerEndpoint,
                                    SamplingService)

# joins the jax.distributed job when the --multihost launcher (or an
# external orchestrator) exported REPRO_COORDINATOR/..; no-op otherwise.
# Must run before the first jax computation.
initialize_distributed()
rank = jax.process_index()
world = jax.process_count()

# 1. problem identification + schema (paper §8.1)
schema = mag_schema()
store, labels = synthetic_mag(n_papers=args.papers,
                              n_authors=args.papers // 2,
                              n_institutions=40, n_fields=80,
                              n_classes=8, feat_dim=32)

# 2. sampling spec (paper Fig. 6) + distributed sampling (§8.2)
b = SamplingSpecBuilder(schema)
seed_op = b.seed("paper")
cited = seed_op.sample(8, "cites")
authors = cited.join([seed_op]).sample(4, "written")
author_papers = authors.sample(4, "writes")
authors.sample(4, "affiliated_with")
author_papers.join([seed_op, cited]).sample(4, "has_topic")
spec = seed_op.build()
print("sampling ops:", [op.op_name for op in spec.sampling_ops])

num_shards = 4
with tempfile.TemporaryDirectory() as tmp:
    n_train = int(args.papers * 0.75)
    shards = distributed_sample(store, spec, range(args.papers), tmp,
                                num_shards=num_shards)
    graphs = [g for p in shards for g in load_graphs(p)]
# roots in shard-file order — graphs[i] is the subgraph rooted at
# root_order[i], sampled with seed_rng(0, root); the sampling service
# reproduces graphs bit-identically from these roots
root_order = np.concatenate(shard_partition(range(args.papers), num_shards))
print(f"sampled {len(graphs)} rooted subgraphs via "
      f"{num_shards} shard workers")
train_graphs = graphs[:n_train]
train_roots = root_order[:n_train]
test_graphs = graphs[n_train:]

# 3. modeling (paper §8.3: 4-round MPNN over all five edge sets)
dim = args.hidden
edges = {name: (es.source, es.target)
         for name, es in schema.edge_sets.items()}
node_dims = {n: dim for n in schema.node_sets}


class InitStates(Module):
    """MapFeatures analogue: paper features -> uniform hidden states;
    id-embedding tables for institutions/fields (paper §8.1)."""

    def __init__(self):
        self.paper = Linear(32, dim)
        self.tables = {n: Embedding(4096, dim)
                       for n in ("author", "institution", "field_of_study")}

    def init(self, key):
        ks = jax.random.split(key, 4)
        p = {"paper": self.paper.init(ks[0])}
        for i, (n, t) in enumerate(sorted(self.tables.items())):
            p[n] = t.init(ks[i + 1])
        return p

    def __call__(self, params, graph):
        ns = {"paper": {HIDDEN_STATE: jax.nn.relu(self.paper(
            params["paper"], graph.node_sets["paper"]["feat"]))}}
        for n, t in self.tables.items():
            ids = graph.node_sets[n]["id"] % 4096
            ns[n] = {HIDDEN_STATE: t(params[n], ids,
                                     dtype=jax.numpy.float32)}
        return graph.replace_features(node_sets=ns)


gnn = vanilla_mpnn(edges, node_dims, message_dim=dim, hidden_dim=dim,
                   num_rounds=4, use_layer_norm=True)

# 4. orchestration (paper §8.4) — the batch is a super-batch of one
# padded component group per DATA shard (= num_devices / model_parallel);
# SizeConstraints are per group, so the same seed trains to the same loss
# at any device count — and at any process count: each jax.distributed
# rank produces its GraphBatcher(rank, world) shard of the same global
# groups, reassembled onto the same global mesh rows.
bs = 16
ndev = args.num_devices
mp = args.model_parallel
if ndev % mp:
    raise SystemExit(f"--model-parallel {mp} must divide "
                     f"--num-devices {ndev}")
rep = ndev // mp  # GLOBAL data shards = component groups per super-batch
if bs % rep:
    raise SystemExit(f"data shards {rep} must divide batch size {bs}")
if rep % world:
    raise SystemExit(f"processes {world} must divide data shards {rep}")
rep_local = rep // world  # this process's component groups per step
sizes = find_size_constraints(graphs, bs // rep)
task = RootNodeMulticlassClassification("paper", 8, dim)


def super_batch_labels(graph):
    """Per-group root labels [R, C] from a stacked super-batch."""
    root_labels = RootNodeMulticlassClassification.root_labels
    arr = np.asarray(graph.node_sets["paper"].sizes)       # [R, C]
    lab = np.asarray(graph.node_sets["paper"]["labels"])   # [R, cap]
    return np.stack([
        root_labels(arr[r], lab[r]) for r in range(arr.shape[0])
    ]).astype(np.int32)


def batches_for(gs):
    batcher = GraphBatcher(gs, bs, sizes, seed=0, rank=rank, world=world,
                           num_replicas=rep_local)

    def gen(epoch):
        for graph in batcher.epoch(epoch):
            yield graph, super_batch_labels(graph)

    return gen


def _endpoint_file() -> str:
    path = os.environ.get("REPRO_ENDPOINT_FILE", "")
    if not path:
        raise SystemExit(
            "multi-process run without REPRO_ENDPOINT_FILE: use "
            "--multihost N (or export the REPRO_* env the launcher sets)")
    return path


def _publish_endpoint(address) -> None:
    """Atomically write the endpoint's (host, port) for the other ranks
    (OS-assigned port: nothing is known before the listener binds)."""
    path = _endpoint_file()
    tmp_path = path + ".tmp"
    with open(tmp_path, "w") as f:
        f.write(f"{address[0]}:{address[1]}")
    os.replace(tmp_path, path)


def _read_endpoint(timeout: float = 120.0):
    path = _endpoint_file()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with open(path) as f:
                host, port = f.read().strip().rsplit(":", 1)
                return host, int(port)
        except (FileNotFoundError, ValueError):
            time.sleep(0.05)
    raise SystemExit(f"rank {rank}: no endpoint address in {path} "
                     f"after {timeout:.0f}s")


run_kwargs = dict(model_fn=lambda: (InitStates(), gnn), task=task,
                  epochs=args.epochs, learning_rate=3e-3, total_steps=600,
                  eval_batches=lambda: batches_for(test_graphs)(0),
                  ckpt_dir="", log_every=20, num_devices=ndev,
                  model_parallel=mp, max_steps=args.steps)
sampler_kind = args.sampler
if world > 1:
    # multi-host: rank 0 hosts the sampler fleets behind a TCP endpoint;
    # every rank (rank 0 included) consumes its own stream through a
    # RemoteStreamClient — batches identical to the in-process
    # GraphBatcher(rank, world) stream, delivered over TCP.
    sampler_kind = "service/tcp"
    endpoint = None
    if rank == 0:
        def rank_fleet(r):
            return SamplingService(store, spec, train_roots, batch_size=bs,
                                   sizes=sizes,
                                   num_workers=args.sampler_workers,
                                   num_replicas=rep_local, seed=0, rank=r,
                                   world=world, base_seed=0)
        endpoint = SamplerEndpoint(rank_fleet)
        _publish_endpoint(endpoint.address)
    client = RemoteStreamClient(_read_endpoint(), rank)
    try:
        result = run(sampler="service", service=client,
                     label_fn=super_batch_labels, **run_kwargs)
    finally:
        client.close()
        if endpoint is not None:
            endpoint.close()
elif args.sampler == "service":
    # same plan (batch_size/seed/num_replicas) + same per-root sampling
    # seeds as the in-process path => bit-identical batches, same loss —
    # but Algorithm 1 + merge + pad run in the worker fleet, not here
    with SamplingService(store, spec, train_roots, batch_size=bs,
                         sizes=sizes, num_workers=args.sampler_workers,
                         num_replicas=rep, seed=0, base_seed=0) as svc:
        result = run(sampler="service", service=svc,
                     label_fn=super_batch_labels, **run_kwargs)
else:
    result = run(train_batches=batches_for(train_graphs), **run_kwargs)
if rank == 0:
    print(f"final loss {result.train_loss:.4f}  "
          f"test accuracy {result.metrics['eval_accuracy']:.4f}  "
          f"({ndev} device(s) = {rep} data x {mp} model over {world} "
          f"process(es), {result.step} steps, {sampler_kind} sampler)")
else:
    print(f"rank {rank}/{world} loss {result.train_loss:.4f} "
          f"({result.step} steps)")
if args.steps is None:  # full runs keep the accuracy gate; --steps N
    assert result.metrics["eval_accuracy"] > 0.5  # smoke runs skip it
print("ogbn_mag_train OK")
