"""The reduction of the program's scopes and spans (`harness/scopes.py`),
on a small trace recorded on the CPU here, and the tool that reads it
beside a cell's traced run."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import scopes, trace
from bench.harness.program import BENCH, load_module
from bench.tests.conftest import run_tiny

STEPS = 3
SAMPLE_S, MERGE_PAD_S = 0.03, 0.015


def _sqsum(x):
    return jax.lax.map(lambda row: jnp.sum(jnp.square(row)), x).sum()


def _loss(p, x):
    with jax.named_scope("gnn"):
        with jax.named_scope("round_0"):
            h = jnp.tanh(x @ p["w"])
    with jax.named_scope("head"):
        return jnp.mean(h ** 2)


@jax.jit
def train_step(p, x):
    loss, g = jax.value_and_grad(_loss)(p, x)
    with jax.named_scope("optimizer"):
        with jax.named_scope("clip"):
            norm = jnp.sqrt(_sqsum(g["w"]))
        with jax.named_scope("update"):
            p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b / norm,
                                       p, g)
    return p, loss


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    p, x = {"w": jnp.ones((256, 256))}, jnp.ones((256, 256))
    train_step(p, x)[1].block_until_ready()
    hlo = train_step.lower(p, x).compile().as_text()
    with trace.Profile(log_dir):
        for k in range(STEPS):
            with trace.span("next"):
                with jax.profiler.TraceAnnotation("repro.sample", step=k):
                    time.sleep(SAMPLE_S)
                with jax.profiler.TraceAnnotation("repro.merge_pad",
                                                  step=k):
                    time.sleep(MERGE_PAD_S)
            with trace.span("step"):
                with jax.profiler.TraceAnnotation("repro.dispatch", step=k):
                    out = train_step(p, x)
                with jax.profiler.TraceAnnotation("repro.readback", step=k):
                    float(out[1])
    path = trace.find_xplane(log_dir)
    return (path, hlo, scopes.reduce_scopes(
        path, scopes.op_scopes(hlo), program="train_step", host_ops=True))


def _ops(path):
    """(start, end, instruction) of every host operation of the step
    program inside the window: the reference the reduction is held to."""
    from jax.profiler import ProfileData
    window, ops = None, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == trace.TRACED:
                    window = iv
                elif (line.name.startswith("tf_XLA") and e.duration_ns
                      and "train_step" in str(dict(e.stats).get(
                          "hlo_module"))):
                    ops.append((*iv, e.name))
    return [(max(s, window[0]), min(e, window[1]), n) for s, e, n in ops
            if e > window[0] and s < window[1]]


def _covered(intervals) -> float:
    return sum(e - s for s, e in trace._union(intervals)) / 1e9


def test_op_scopes_read_the_name_stack(recorded):
    _, hlo, _ = recorded
    by_op = scopes.op_scopes(hlo)
    loops = [n for n in by_op if n.startswith("while")]
    assert loops and all({"optimizer", "clip"} <= by_op[n] for n in loops)
    assert any({"gnn", "round_0"} <= v for v in by_op.values())
    assert any({"optimizer", "update"} <= v for v in by_op.values())
    # adjacent scopes also read as one name, a round's sets among them
    assert all("optimizer/clip" in by_op[n] for n in loops)
    assert any("gnn/round_0" in v for v in by_op.values())
    assert not any("gnn/head" in v for v in by_op.values())
    # primitive names are not scopes
    assert not any("tanh" in v or "dot_general" in v for v in by_op.values())


def test_forward_and_backward_both_count(recorded):
    path, hlo, sc = recorded
    stacks = dict(
        (m.group(1), m.group(2)) for m in map(scopes._OP_NAME.match,
                                              hlo.splitlines()) if m)
    ops = _ops(path)
    fwd = [(s, e) for s, e, n in ops if "/jvp(gnn)/" in stacks.get(n, "")]
    bwd = [(s, e) for s, e, n in ops
           if "/transpose(jvp(gnn))/" in stacks.get(n, "")]
    assert fwd and bwd
    assert sc.scope_seconds("gnn") == pytest.approx(_covered(fwd + bwd),
                                                    rel=1e-9)
    assert sc.scope_seconds("gnn") > max(_covered(fwd), _covered(bwd))


def test_while_body_counts_once(recorded):
    path, hlo, sc = recorded
    by_op = scopes.op_scopes(hlo)
    clip = [(s, e) for s, e, n in _ops(path)
            if "clip" in by_op.get(n, ())]
    summed = sum(e - s for s, e in clip) / 1e9
    assert sc.scope_seconds("clip") == pytest.approx(_covered(clip),
                                                     rel=1e-9)
    # the loop's body operations lie inside the loop's own event
    assert sc.scope_seconds("clip") < summed


def test_scopes_lie_within_the_program(recorded):
    path, _, sc = recorded
    red = trace.reduce_trace(path, host_ops=True)
    named = [sc.scope_seconds(s) for s in ("gnn", "head", "optimizer")]
    assert all(v > 0 for v in named)
    assert sum(named) <= sc.program_s * (1 + 1e-9)
    assert sc.scope_seconds("gnn", "head", "optimizer") <= sc.program_s
    assert sc.program_s <= red.module_seconds("train_step")[0] + 1e-9
    assert sc.scope_seconds("clip", "update") <= sc.scope_seconds(
        "optimizer") + 1e-9


def test_spans_and_idle_under_them(recorded):
    path, _, sc = recorded
    red = trace.reduce_trace(path, host_ops=True)
    assert sc.window_s == pytest.approx(red.window_s)
    assert STEPS * SAMPLE_S <= sc.span_s["repro.sample"] < 2 * STEPS * \
        SAMPLE_S
    assert STEPS * MERGE_PAD_S <= sc.span_s["repro.merge_pad"]
    assert sc.span_s["bench.next"] >= (sc.span_s["repro.sample"]
                                       + sc.span_s["repro.merge_pad"])
    # every idle second is labelled once, and the sleeps are idle
    assert sum(sc.idle_under.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert sc.idle_under["repro.sample"] == pytest.approx(
        sc.span_s["repro.sample"], rel=0.02)
    assert sc.idle_under["repro.merge_pad"] == pytest.approx(
        sc.span_s["repro.merge_pad"], rel=0.02)
    longest, parts = sc.gaps[0]
    assert longest > SAMPLE_S + MERGE_PAD_S
    assert max(parts, key=parts.get) == "repro.sample"
    assert sum(parts.values()) == pytest.approx(longest)


def test_existing_reduction_reads_no_program_span(recorded):
    """The program's spans leave every field of `trace.Reduced`, and so
    every existing reader, as they were: its gaps are labelled by the
    benchmark's own spans only."""
    path, _, _ = recorded
    red = trace.reduce_trace(path, host_ops=True)
    assert {label for label, _ in red.gaps} <= {"next", "step", "other"}
    assert [label for label, _ in red.gaps[:STEPS]] == ["next"] * STEPS
    secs, runs = red.module_seconds("train_step")
    assert runs == STEPS and 0 < secs < red.window_s
    run = {"records": {"step_program": "train_step",
                       "traced_steps": STEPS}, "trace": red}
    for name, value in (("idle_share.train", 100 * red.idle_share),
                        ("step_device_ms.train", 1e3 * secs / STEPS)):
        reader = load_module(BENCH / "metrics" / f"{name}.py")
        assert reader.read(run) == value


def test_layer_metrics(recorded):
    _, _, sc = recorded
    got = scopes.layer_metrics(sc, traced_steps=STEPS, window_compiles=0)
    # this step has no `pool` scope, so no pool number
    assert set(got) == {"optimizer_device_ms.train", "gnn_device_ms.train",
                        "sample_ms.train", "merge_pad_ms.train",
                        "input_idle_share.train", "step_compiles.train"}
    assert got["sample_ms.train"] == pytest.approx(
        1e3 * sc.span_s["repro.sample"] / STEPS)
    assert 0 < got["input_idle_share.train"] < 100
    # a program without the scopes, spans and counter gives nothing
    bare = scopes.Scoped(window_s=sc.window_s, chips=1, program_s=0.0,
                         span_s={"bench.next": 1.0}, idle_under={},
                         gaps=[])
    assert scopes.layer_metrics(bare, traced_steps=STEPS) == {}


def test_instruction_names():
    assert scopes.instruction(
        "%fusion.40 = (f32[8,128]{1,0:T(8,128)}) fusion(%p0)") == "fusion.40"
    assert scopes.instruction("%while = (s32[]) while(%t)") == "while"
    assert scopes.instruction("dot_general.2") == "dot_general.2"


def test_tool_reads_a_traced_cell(tiny_cache):
    """`tools/program_spans.py` around a tiny sampler-fed cell: every
    metric, the step's program unchanged by its scopes, no compile in
    the window."""
    tool = load_module(BENCH / "tools" / "program_spans.py")
    with tool.capture("train_step") as got:
        result, _, _ = run_tiny("tiny_mpnn", "tiny_train", tiny_cache,
                                trace=True)
    read = tool.readings(got, result)
    assert set(read["metrics"]) == {
        "optimizer_device_ms.train", "gnn_device_ms.train",
        "pool_device_ms.train", "sample_ms.train", "merge_pad_ms.train",
        "input_idle_share.train", "step_compiles.train"}
    assert read["metrics"]["step_compiles.train"] == 0
    assert read["accept"]["outside_optimizer_named_share"] > 80
    # the scopes are read from the executable that ran, not a new build
    assert read["hlo_from"] == "memory"
    # both rounds, and in each the node sets it updates (the last
    # round's author update reaches no root, and XLA drops it)
    rounds = read["round_ms"]
    assert {"round_0", "round_1", "round_0/author", "round_0/paper",
            "round_1/paper"} <= set(rounds)
    assert all(v > 0 for v in rounds.values())
    assert rounds["round_0/paper"] <= rounds["round_0"] + 1e-9
