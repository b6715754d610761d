"""CPU rehearsal of chip_smoke.py: its phases at a tiny graph.

The script itself refuses to run without a TPU (`main` owns that check
and is tested here too); the phase functions it calls are the same on
any backend, with the Pallas kernels in interpret mode off the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dict(n_papers=300, n_authors=150, n_institutions=20, n_fields=40,
            feat_dim=16, n_classes=5, dim=16, rounds=2, roots_per_step=16,
            steps=chip_smoke.TRAIN_STEPS)


@pytest.fixture(scope="module")
def setup():
    return chip_smoke.build_setup(seed=0, **TINY)


@pytest.fixture(scope="module")
def ref(setup):
    return chip_smoke.phase_train_reference(setup)


def test_reference_path_trains_every_step_to_a_finite_loss(ref):
    assert len(ref.losses) == chip_smoke.TRAIN_STEPS
    assert np.all(np.isfinite(ref.losses))
    assert ref.losses[-1] != ref.losses[0]  # the optimizer moved


def test_tight_sizes_hold_each_batch_and_no_more(setup):
    plan = setup.plan()
    order = plan.order(0, len(setup.roots))
    totals = []
    for step in range(plan.num_steps(len(setup.roots))):
        graphs = [chip_smoke.sample_subgraph(
            setup.store, setup.spec, int(setup.roots[i]),
            chip_smoke.seed_rng(setup.seed, int(setup.roots[i])))
            for i in plan.step_indices(order, step)]
        totals.append(sum(g.node_sets["author"].capacity for g in graphs))
    assert setup.sizes.total_num_nodes["author"] == max(totals) + 1
    assert setup.sizes.total_num_components == TINY["roots_per_step"] + 1


def test_kernel_path_runs_kernels_and_matches_the_reference(setup, ref):
    run = chip_smoke.phase_train_kernels(setup, ref)
    ran = [d for *_, d in run.decisions if d is not None and d.use_kernel]
    assert any(d.variant == "runs" for d in ran)
    # off the chip every kernel runs in interpret mode, which main()
    # refuses on a TPU
    assert all(d.interpret for d in ran)
    rounds = {r for r, *_ in run.decisions}
    assert rounds == set(range(TINY["rounds"]))


def test_sampler_fleet_losses_equal_the_in_process_run(setup, ref):
    run = chip_smoke.phase_sampler_fleet(setup, ref)
    assert run.losses == ref.losses[:chip_smoke.FLEET_STEPS]


def test_served_answers_match_direct_forwards(setup, ref):
    out = chip_smoke.phase_serve(setup, ref.params, n_requests=12)
    assert out["served"] == 12 and out["recompiles"] == 0


def test_mesh_phase_matches_one_device_over_four_cpu_devices():
    """The --chips 4 phase, in its own process: JAX fixes the device
    count at start-up."""
    code = ("import chip_smoke as cs; cs.phase_mesh("
            f"cs.build_setup(seed=0, **{TINY!r}), num_devices=4)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"))
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    for name in ("mesh[4x1]", "mesh[2x2]"):
        assert f"{name}: batch and parameters on 4 distinct devices" \
            in res.stdout
        assert f"{name} vs 1-device run" in res.stdout


def test_main_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script-alone"])
def test_script_exits_nonzero_and_prints_no_result_on_cpu(alone, tmp_path):
    script = ROOT / "chip_smoke.py"
    if alone:  # a directory holding the script and nothing else
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
