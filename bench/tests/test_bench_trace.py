"""The trace reduction, on a small trace recorded on the CPU here."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import trace


def test_union_and_clip():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3],
                                                              [5, 9]]
    assert trace._clip([(0, 4), (5, 9), (10, 12)], 2, 8) == [(2, 4), (5, 8)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    step(x).block_until_ready()
    host = []
    with trace.Profile(log_dir):
        t0 = time.perf_counter()
        for _ in range(3):
            with trace.span("step"):
                step(x).block_until_ready()
            with trace.span("next"):
                time.sleep(0.02)
        host.append(time.perf_counter() - t0)
    return log_dir, host[0]


@pytest.fixture(scope="module")
def reduced(recorded):
    log_dir, host_s = recorded
    return (trace.reduce_trace(trace.find_xplane(log_dir), host_ops=True),
            host_s)


def test_no_device_plane_is_refused(recorded):
    """A device metric is never read from the host's operations unless
    the caller asks for them."""
    log_dir, _ = recorded
    with pytest.raises(RuntimeError, match="no device operation"):
        trace.reduce_trace(trace.find_xplane(log_dir))


def test_busy_idle_and_programs(reduced):
    red, host_s = reduced
    assert red.chips == 1
    assert abs(red.window_s - host_s) < 0.05
    assert 0 < red.busy_s < red.window_s
    assert 0.5 < red.idle_share < 1.0   # three 20 ms sleeps dominate
    secs, runs = red.module_seconds("lambda")
    # a CPU program's span runs from its first op to its last
    assert runs == 3 and 0.9 * red.busy_s <= secs < red.window_s
    names = [n for n, _ in red.top_ops]
    assert any("dot" in n for n in names)


def test_gaps_labelled_by_host_span(reduced):
    red, _ = reduced
    labels = [label for label, _ in red.gaps]
    assert labels[:3] == ["next"] * 3
    assert all(s > 0.015 for _, s in red.gaps[:3])
    assert red.gaps == sorted(red.gaps, key=lambda g: -g[1])
