"""Trainer — the loop-owning piece of the orchestration layer.

The Trainer owns exactly three things (TF-GNN paper §5: the runner's
Trainer protocol), each delegated to the layer that already implements
it:

  * the **mesh** — `partition.MeshPlan` via ``num_devices``/
    ``model_parallel`` (2-D ("data", "model") sharding, multi-host aware);
  * the **step functions** — `train_loop.make_graph_train_step` /
    `make_graph_eval_step` (plain jit single-device, `partition`
    shard_map factories under a plan);
  * the **checkpoint lifecycle** — `fault_tolerance.CheckpointManager`:
    periodic async saves carrying the data-pipeline offset
    (``extra={"epoch", "step_in_epoch"}``), preemption-safe
    ``resume=True`` through `restore_latest` + the DatasetProvider's
    ``epoch(e, start_step=s)`` replay, and best-checkpoint tracking
    (`mark_best`) driven by the eval stream.

What it does NOT own: the objective (the `Task` — head, labels, loss,
metrics) and the stream (the `DatasetProvider`).  ``Trainer.fit`` wires
the three together; `runner.run` is now a thin shim over this class, and
its loss trajectory is bit-for-bit the seed runner's (pinned in
tests/test_runner_parity.py) because every composition choice below —
key splits, optimizer schedule, loss closure, lazy step construction,
layout hint scope — is unchanged.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.distributed.fault_tolerance import CheckpointManager
from repro.kernels import dispatch as kernel_dispatch
from repro.nn.module import split_params
from repro.orchestration.evaluation import EarlyStopping, evaluate
from repro.runtime import compile_count
from repro.train.optimizer import AdamW, warmup_cosine
from repro.train.train_loop import (device_prefetch, make_graph_eval_step,
                                    make_graph_train_step)


@dataclasses.dataclass
class RunResult:
    step: int
    train_loss: float
    metrics: dict


@dataclasses.dataclass
class Trainer:
    """Optimization-loop configuration; `fit` runs it.

    Scheduling (``learning_rate``/``warmup_steps``/``total_steps``/
    ``weight_decay``) reproduces the repo-standard AdamW + warmup-cosine
    recipe.  ``eval_at`` places the validation pass: "end" (once, after
    all epochs — the legacy runner contract), "epoch" (after every epoch:
    the early-stopping + best-checkpoint mode), or "never".

    ``resume=True`` restores the latest checkpoint in ``ckpt_dir`` (if
    any) and re-enters the stream at the exact (epoch, step) the
    checkpoint recorded — with every DatasetProvider honouring the
    ``(seed, epoch, step) -> batch`` purity contract, a killed-and-
    resumed run's loss sequence is identical to an uninterrupted one
    (pinned in tests/test_checkpoint_resume.py).
    """

    epochs: int = 1
    learning_rate: float = 1e-3
    total_steps: int = 1000
    warmup_steps: int = 50
    weight_decay: float = 1e-5
    seed: int = 0
    num_devices: Optional[int] = None
    model_parallel: int = 1
    max_steps: Optional[int] = None
    log_every: int = 20
    double_buffer: bool = False
    edges_sorted_by_target: Optional[bool] = None
    ckpt_dir: str = ""
    keep: int = 3
    save_interval_steps: int = 100
    resume: bool = False
    eval_at: str = "end"
    early_stopping: Optional[EarlyStopping] = None
    track_best: bool = True

    def __post_init__(self):
        if self.eval_at not in ("end", "epoch", "never"):
            raise ValueError(f"eval_at must be 'end', 'epoch' or 'never', "
                             f"got {self.eval_at!r}")

    # -- wiring ---------------------------------------------------------------

    def _init_params(self, init_states, gnn, head) -> dict:
        key = jax.random.PRNGKey(self.seed)
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "init": split_params(init_states.init(k1))[0],
            "gnn": split_params(gnn.init(k2))[0],
            "head": split_params(head.init(k3))[0],
        }

    def _make_plan(self):
        if self.num_devices is not None:
            from repro.distributed import partition
            return partition.make_plan(self.num_devices,
                                       model_parallel=self.model_parallel)
        if self.model_parallel > 1:
            raise ValueError("model_parallel > 1 needs num_devices=")
        if jax.process_count() > 1:
            raise ValueError(
                "multi-process (jax.distributed) training needs "
                "num_devices= — the per-process jit path cannot see the "
                "global mesh")
        return None

    @staticmethod
    def _labeled(stream, task, epoch: int, start_step: int):
        """Normalize a provider stream to (step, graph, labels): sources
        that pre-compute labels pass through; bare graphs go through the
        Task's extraction at the stream's (epoch, step) coordinates."""
        for step, item in enumerate(stream, start=start_step):
            if isinstance(item, tuple):
                yield (step, *item)
            else:
                with jax.profiler.TraceAnnotation("repro.labels",
                                                  epoch=epoch, step=step):
                    labels = task.labels(item, epoch=epoch, step=step)
                yield step, item, labels

    def fit(self, model_fn: Callable, task, train_provider, *,
            eval_provider=None) -> RunResult:
        """Train `task` over `train_provider`; returns the final step,
        last train loss, and a metrics dict with "params",
        "train_losses" (every step this call ran, in order) and
        "step_compiles" (the train step's programs compiled so far,
        after each of those steps; None where the step is not a jitted
        function); + "eval", "eval_history", "best_step" when an eval
        stream ran.

        Under the JAX profiler each step leaves the host spans
        ``repro.labels`` (`Task.labels`), ``repro.place``,
        ``repro.dispatch`` (the jitted step call) and ``repro.readback``
        (``float(loss)``, where the host waits for the chip), each with
        the stream's ``epoch`` and ``step`` as stats, beside the
        provider's own ``repro.sample``/``repro.merge_pad``."""
        init_states, gnn = model_fn()
        head = task.head()
        params = self._init_params(init_states, gnn, head)
        opt = AdamW(learning_rate=warmup_cosine(
                        self.learning_rate, self.warmup_steps,
                        self.total_steps),
                    weight_decay=self.weight_decay)
        opt_state = opt.init(params)

        def loss_fn(params, graph, labels):
            with jax.named_scope("init_states"):
                graph = init_states(params["init"], graph)
            with jax.named_scope("gnn"):
                graph_out = gnn(params["gnn"], graph)
            with jax.named_scope("head"):
                return task.loss_from_graph(params["head"], graph_out,
                                            labels)

        metric_keys = tuple(task.metric_names())

        def metric_fn(params, graph, labels):
            graph_out = gnn(params["gnn"], init_states(params["init"],
                                                       graph))
            pairs = task.metrics(params["head"], graph_out, labels)
            if tuple(sorted(pairs)) != metric_keys:
                raise ValueError(
                    f"{type(task).__name__}.metrics keys "
                    f"{tuple(sorted(pairs))} != metric_names() "
                    f"{metric_keys}")
            flat = []
            for k in metric_keys:
                num, den = pairs[k]
                flat += [num, den]
            return tuple(flat)

        plan = self._make_plan()
        # one process narrates / checkpoints for the whole job; the others
        # compute the same replicated results and stay quiet
        is_main = jax.process_index() == 0
        if self.ckpt_dir and jax.process_count() > 1:
            # fail fast, not at step save_interval: save_async
            # materializes the full state host-side, and ZeRO-1 optimizer
            # shards live on other processes' devices
            raise ValueError(
                "checkpointing (ckpt_dir=) is not yet supported under "
                "multi-process jax.distributed — optimizer state is "
                "sharded across processes; run with ckpt_dir=''")

        esbt = self.edges_sorted_by_target
        if esbt is None:
            esbt = train_provider.edges_sorted_by_target
        if esbt is None:
            esbt = True  # the repo-wide producer default

        def place(graph, labels):
            """Host batch -> device batch (the plan's 2-D sharding in
            mesh mode, so double-buffered placement lands pre-sharded)."""
            if plan is not None:
                return plan.put_super_batch(graph, labels)
            return (jax.tree_util.tree_map(jnp.asarray, graph),
                    jnp.asarray(labels))

        mgr = CheckpointManager(
            self.ckpt_dir, keep=self.keep,
            save_interval_steps=self.save_interval_steps) \
            if self.ckpt_dir else None
        step = 0
        start_epoch = 0
        epoch_start_step = 0
        if mgr is not None and self.resume:
            restored = mgr.restore_latest((params, opt_state))
            if restored is not None:
                step, (params, opt_state), extra = restored
                start_epoch = int(extra.get("epoch", 0))
                epoch_start_step = int(extra.get("step_in_epoch", 0))

        single_train_step = None if plan is not None else \
            make_graph_train_step(loss_fn, opt)
        single_eval_step = None if plan is not None else \
            make_graph_eval_step(metric_fn)
        dp_train_step = dp_eval_step = None

        monitor = self.early_stopping or (
            # best-tracking without early stopping: an unreachable
            # patience makes `update` pure best bookkeeping
            EarlyStopping(monitor="loss", patience=2 ** 62, mode="min")
            if eval_provider is not None and self.eval_at == "epoch"
            else None)
        stop_early = False
        eval_history = []
        train_losses = []
        step_compiles = []
        last_loss = float("nan")
        cur_epoch = start_epoch
        step_in_epoch = epoch_start_step
        t0 = time.time()

        def run_eval():
            nonlocal dp_eval_step
            if plan is not None and dp_eval_step is None:
                from repro.distributed import partition
                dp_eval_step = partition.make_eval_step(plan, metric_fn)
            step_fn = dp_eval_step if plan is not None else single_eval_step
            return evaluate(eval_provider, task,
                            lambda g, l: step_fn(params, g, l), place,
                            metric_keys=metric_keys)

        def save(at_step, epoch, step_in_epoch):
            mgr.save_async(at_step, (params, opt_state),
                           extra={"epoch": epoch,
                                  "step_in_epoch": step_in_epoch})

        # the layout hint is read at trace time by kernel dispatch, so the
        # context must enclose the first train/eval step (where jit traces)
        with kernel_dispatch.layout(sorted_by_target=esbt):
            for epoch in range(start_epoch, self.epochs):
                if self.max_steps is not None and step >= self.max_steps:
                    break
                start = epoch_start_step if epoch == start_epoch else 0
                cur_epoch = epoch
                pairs = self._labeled(
                    train_provider.epoch(epoch, start_step=start),
                    task, epoch, start)

                def place_step(k, graph, labels, epoch=epoch):
                    with jax.profiler.TraceAnnotation(
                            "repro.place", epoch=epoch, step=k):
                        return place(graph, labels)

                if self.double_buffer:
                    placed = device_prefetch(pairs, place_step)
                else:
                    placed = (place_step(*p) for p in pairs)
                step_in_epoch = start
                for graph, labels in placed:
                    if self.max_steps is not None \
                            and step >= self.max_steps:
                        placed.close()  # joins the device_prefetch thread
                        break
                    if plan is not None and dp_train_step is None:
                        from repro.core.graph_tensor import stack_size
                        dp_train_step = make_graph_train_step(
                            loss_fn, opt, plan=plan,
                            num_groups=stack_size(graph))
                        params = plan.replicate(params)
                        # ZeRO-1: AdamW m/v land "data"-sharded
                        opt_state = plan.place_opt_state(opt, params,
                                                         opt_state)
                    train_step = (dp_train_step if plan is not None
                                  else single_train_step)
                    with jax.profiler.TraceAnnotation(
                            "repro.dispatch", epoch=epoch,
                            step=step_in_epoch):
                        params, opt_state, loss = train_step(
                            params, opt_state, graph, labels)
                    with jax.profiler.TraceAnnotation(
                            "repro.readback", epoch=epoch,
                            step=step_in_epoch):
                        last_loss = float(loss)
                    step += 1
                    step_in_epoch += 1
                    train_losses.append(last_loss)
                    step_compiles.append(compile_count(train_step))
                    if step % self.log_every == 0 and is_main:
                        print(f"epoch {epoch} step {step} "
                              f"loss {last_loss:.4f} "
                              f"({self.log_every / (time.time() - t0):.1f}"
                              f" it/s)", flush=True)
                        t0 = time.time()
                    if mgr is not None and is_main \
                            and mgr.should_save(step):
                        save(step, epoch, step_in_epoch)
                if eval_provider is not None and self.eval_at == "epoch":
                    em = run_eval()
                    eval_history.append(em)
                    if is_main:
                        print(f"epoch {epoch} eval "
                              + " ".join(f"{k} {v:.4f}"
                                         for k, v in sorted(em.items())),
                              flush=True)
                    if monitor is not None:
                        is_best = monitor.update(em[monitor.monitor],
                                                 step=step)
                        if (is_best and self.track_best and mgr is not None
                                and is_main):
                            # pin this step's weights as `best` (save
                            # synchronously so the pointer has a target)
                            save(step, epoch, step_in_epoch)
                            mgr.wait()
                            mgr.mark_best(step)
                        if monitor.should_stop:
                            stop_early = True
                            break

            metrics = {}
            if eval_provider is not None and self.eval_at == "end":
                em = run_eval()
                eval_history.append(em)
                metrics["eval"] = em
        if mgr is not None and is_main:
            save(step, cur_epoch, step_in_epoch)
            mgr.wait()
        if eval_history:
            metrics.setdefault("eval", eval_history[-1])
            metrics["eval_history"] = eval_history
        if monitor is not None and monitor.best_step is not None:
            metrics["best_step"] = monitor.best_step
            metrics["best_value"] = monitor.best
        if stop_early:
            metrics["stopped_early"] = True
        metrics["params"] = params
        metrics["train_losses"] = train_losses
        metrics["step_compiles"] = step_compiles
        return RunResult(step, last_loss, metrics)
