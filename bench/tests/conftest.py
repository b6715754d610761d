"""CPU rehearsals of the benchmark at tiny sizes.

The tiny configurations and traffic files beside these tests are data,
like the real ones under bench/configs and bench/traffic; the tests
drive the same harness with the chip check skipped."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
# Between what the program reads on the CPU at these sizes (exact float32
# matmuls: gaps under 4e-7) and what the control reads (matmuls at three
# bfloat16 passes: 1.4e-6 on the first losses, 1.2e-5 on gradients).
LIMITS = {"sample_faults": 0, "loss_gap": 1e-6, "grad_gap": 3e-6,
          "head_grad_gap": 3e-6,
          "change_gap": 3e-6, "unanswered": 0, "logit_gap": 3e-6}


def load(kind: str, name: str) -> dict:
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench-cache")


def run_tiny(config, traffic, cache, *, trace=False, seed=2 ** 33 + 5,
             seconds=1.5):
    """One run of a tiny cell on the CPU, the chip check skipped."""
    import jax
    from bench.harness import runner
    tr = load("traffic", traffic)
    train = tr["driver"] == "train"
    e2e = (["train_roots_per_s", "setup_s"] if train
           else ["serve_rps", "setup_s"])
    layers = (["input_ms.train", "pad_share.train", "step_device_ms.train",
               "mfu.train", "idle_share.train"] if train else
              ["batch_fill.serve", "latency_p95_ms.serve",
               "forward_device_ms.serve", "mfu.serve", "idle_share.serve"])
    return runner.run_cell(
        f"{config}.{traffic}", load("configs", config), tr, LIMITS,
        seed=seed, seconds=seconds, trace=trace, chips=1, e2e=e2e,
        layers=layers, devices_fn=lambda n: jax.devices()[:n],
        peaks_fn=lambda kind: TINY_PEAKS, cache_dir=cache,
        host_trace=True), e2e, layers
