"""The ``train_mesh`` driver on the CPU at the tiny sizes: through
``presampled`` at one device and through a forked ``service`` at two
(in a subprocess with two host devices), each agreeing with the
reference and reading the step's scopes into ``records["scope_ms"]``;
a planted fault, and a mesh step whose devices do not exchange their
gradients, are not correct."""
import contextlib
import json
import os
import subprocess
import sys

from bench.harness import faults
from bench.tests.conftest import LIMITS, ROOT, TINY_PEAKS, load

LAYERS = ["input_ms.train", "pad_share.train", "step_device_ms.train",
          "mfu.train", "idle_share.train", "gnn_device_ms.train",
          "attention_device_ms.train"]


def run_mesh(config, traffic, cache, *, trace, seed=2 ** 33 + 9):
    """One tiny run through `train_mesh` with the training metrics and
    the scope readers; the traffic names its device count."""
    import jax
    from bench.harness import runner
    tr = load("traffic", traffic)
    return runner.run_cell(
        f"{config}.{traffic}", load("configs", config), tr, LIMITS,
        seed=seed, seconds=1.5, trace=trace, chips=tr["num_devices"],
        e2e=["train_roots_per_s", "setup_s"], layers=LAYERS,
        devices_fn=lambda n: jax.devices()[:n],
        peaks_fn=lambda kind: TINY_PEAKS, cache_dir=cache, host_trace=True)


def _check_traced(line, scopes):
    assert line["correct"], line["checks"]
    metrics = line["metrics"]
    for name in LAYERS[:5]:
        assert name in metrics, name
    assert 0 < metrics["pad_share.train"]["value"] < 100
    assert metrics["mfu.train"]["value"] > 0
    gnn = metrics["gnn_device_ms.train"]["value"]
    assert 0 < gnn < metrics["step_device_ms.train"]["value"]
    if "hgt_attention" in scopes:
        assert 0 < metrics["attention_device_ms.train"]["value"] < gnn
    else:
        assert "attention_device_ms.train" not in metrics


def test_presampled_one_device(tiny_cache):
    line = run_mesh("tiny_hgt", "tiny_presampled_scoped", tiny_cache,
                    trace=True)
    assert line["device"]["count"] == 1
    _check_traced(line, ("gnn", "hgt_attention"))


def test_untraced_window_line(tiny_cache):
    line = run_mesh("tiny_hgt", "tiny_presampled_scoped", tiny_cache,
                    trace=False)
    assert line["correct"], line["checks"]
    assert sorted(line["metrics"]) == ["setup_s", "train_roots_per_s"]
    assert line["metrics"]["train_roots_per_s"]["value"] > 0


def test_planted_fault_is_not_correct(tiny_cache):
    with faults.FAULTS["half_batch"]():
        line = run_mesh("tiny_hgt", "tiny_presampled_scoped", tiny_cache,
                        trace=False)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@contextlib.contextmanager
def no_gradient_exchange():
    """The mesh step leaves out its cross-device gradient reduction: each
    device updates from its own group's gradient (a ZeRO-sliced leaf from
    its slice of it), while the loss is still averaged over devices."""
    from repro.distributed import partition
    plan_reduce, pmean = partition.MeshPlan.zero_reduce_grads, partition._pmean
    partition.MeshPlan.zero_reduce_grads = (
        lambda self, grads, dims: self.zero_slice(grads, dims))
    partition._pmean = lambda tree, axis: tree
    try:
        yield
    finally:
        partition.MeshPlan.zero_reduce_grads = plan_reduce
        partition._pmean = pmean


SERVICE = """
import contextlib, json, sys
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests.test_bench_train_mesh import no_gradient_exchange, run_mesh
with no_gradient_exchange() if {fault!r} else contextlib.nullcontext():
    line = run_mesh("tiny_mpnn", "tiny_train_dp", Path({cache!r}),
                    trace={trace!r})
print(json.dumps(line))
"""


def run_two_devices(cache, *, trace: bool, fault: bool) -> dict:
    code = SERVICE.format(root=str(ROOT), src=str(ROOT / "src"),
                          cache=str(cache), trace=trace, fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 2
    return line


def test_service_two_devices(tiny_cache):
    """A forked `SamplingService` feeds a 2 x 1 mesh: each step is two
    groups of 16 roots, one per device, and the reference merges all 32."""
    _check_traced(run_two_devices(tiny_cache, trace=True, fault=False),
                  ("gnn",))


def test_two_devices_without_gradient_exchange(tiny_cache):
    """With the gradient exchange left out, the first clipped gradient
    and the change after three steps leave their limits."""
    checks = run_two_devices(tiny_cache, trace=False, fault=True)["checks"]
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert {"grad_gap", "change_gap"} <= failed, checks


def test_stacked_batches_count_every_group(tiny_cache):
    """The clock counts real items over every group of a super-batch,
    not over all but the last group."""
    from repro.core.graph_tensor import stack_graphs
    from repro.data import find_size_constraints, merge_and_pad
    from repro.data.sampling import sample_subgraph, seed_rng
    from bench.harness import dataset
    from bench.harness.program import (BENCH, Program, load_module,
                                       real_counts)
    mesh = load_module(BENCH / "drivers" / "train_mesh.py")
    cfg = load("configs", "tiny_mpnn")
    store, _ = dataset.load_store(cfg["dataset"], tiny_cache)
    spec = Program(cfg, store).spec
    graphs = [sample_subgraph(store, spec, r, seed_rng(0, r))
              for r in (3, 4, 5, 6)]
    sizes = find_size_constraints(graphs, 2)
    groups = [merge_and_pad(graphs[:2], sizes),
              merge_and_pad(graphs[2:], sizes)]
    clock = mesh.GroupClock.__new__(mesh.GroupClock)
    clock.counts, clock.padded = [], []
    clock._count(stack_graphs(groups))
    each = [real_counts(g) for g in groups]
    assert clock.counts[0]["components"] == 4
    assert clock.counts[0]["edges"] == {
        k: each[0]["edges"][k] + each[1]["edges"][k]
        for k in each[0]["edges"]}
    cap = sum(s.capacity for s in groups[0].node_sets.values()) + sum(
        s.capacity for s in groups[0].edge_sets.values())
    real = sum(sum(c["nodes"].values()) + sum(c["edges"].values())
               for c in each)
    assert clock.padded == [(real, 2 * cap)]
