"""Each driver runs a short window on the CPU and yields the contract's
result line; the traced run yields every per-layer metric."""
import json

import pytest

from bench.tests.conftest import run_tiny

CELLS = [("tiny_mpnn", "tiny_train"), ("tiny_rgcn", "tiny_presampled"),
         ("tiny_mpnn", "tiny_serve")]


def _contract(result):
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for name, check in line["checks"].items():
        assert set(check) == {"value", "limit"}, name
    return line


@pytest.mark.parametrize("config,traffic", CELLS)
def test_window_line(config, traffic, tiny_cache):
    result, e2e, _ = run_tiny(config, traffic, tiny_cache)
    line = _contract(result)
    assert sorted(line["metrics"]) == sorted(e2e)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_traced_line(config, traffic, tiny_cache):
    result, _, layers = run_tiny(config, traffic, tiny_cache, trace=True)
    line = _contract(result)
    assert sorted(line["metrics"]) == sorted(layers)
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_train_and_serve_agree_with_reference(tiny_cache):
    """The VanillaMPNN's train and serve paths match the plain reference
    at the tiny size (these tiny samples have no empty edge set)."""
    for traffic in ("tiny_train", "tiny_serve"):
        result, _, _ = run_tiny("tiny_mpnn", traffic, tiny_cache)
        assert result["correct"], result["checks"]
