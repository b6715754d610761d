"""With the timed path broken underneath, `correct` comes out false:
for training a step that returns its state unchanged and a loss over
half the batch; for serving an answer altered where it is produced; for
both the pool over has_topic left empty.  One chip has no exchange
between chips to leave out.  The control (the reference with its
matmuls at 'high', three bfloat16 passes, in the program's place) fails
the harness's own comparison too."""
import pytest

from bench.harness import faults
from bench.tests.conftest import LIMITS, load, run_tiny


@pytest.mark.parametrize("fault,traffic", [
    ("unchanged_state", "tiny_train"), ("half_batch", "tiny_train"),
    ("altered_answer", "tiny_serve"), ("no_topic_pool", "tiny_train"),
    ("no_topic_pool", "tiny_serve")])
def test_fault_is_not_correct(fault, traffic, tiny_cache):
    with faults.FAULTS[fault]():
        result, _, _ = run_tiny("tiny_mpnn", traffic, tiny_cache)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("traffic", ["tiny_train", "tiny_serve"])
def test_control_is_not_correct(traffic, tiny_cache):
    import jax
    from bench.harness import runner
    tr = load("traffic", traffic)
    ctx = runner.Ctx("tiny", load("configs", "tiny_mpnn"), tr, 2 ** 32 + 3,
                     1.5, False, jax.devices()[:1], 0.0, LIMITS, tiny_cache)
    checks = runner.control_checks(ctx)
    assert checks.values and checks.correct is False, checks.as_dict()
