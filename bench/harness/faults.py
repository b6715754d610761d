"""Faults planted in the program, to show that `correct` catches them.

Used by the CPU tests and by ``bench/tools/readings.py`` on the chip; a
benchmark run never plants one.  Each is a context manager that patches
the program while it is open.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _patched(obj, name, value):
    original = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield original
    finally:
        setattr(obj, name, original)


@contextlib.contextmanager
def unchanged_state():
    """The train step returns its parameters and optimizer state as it
    got them (the loss is still computed)."""
    from repro.orchestration import trainer as trainer_mod
    original = trainer_mod.make_graph_train_step

    def make(loss_fn, opt, **kw):
        step = original(loss_fn, opt, **kw)

        def stuck(params, opt_state, graph, labels):
            _, _, loss = step(params, opt_state, graph, labels)
            return params, opt_state, loss
        return stuck

    with _patched(trainer_mod, "make_graph_train_step", make):
        yield


@contextlib.contextmanager
def half_batch():
    """The loss leaves out the second half of the batch's roots and
    takes the mean over the rest."""
    from repro.orchestration.tasks import RootNodeMulticlassClassification
    original = RootNodeMulticlassClassification.loss

    def loss(self, logits, labels, weights):
        half = jnp.arange(weights.shape[0]) >= weights.sum() // 2
        return original(self, logits, labels, jnp.where(half, 0.0, weights))

    with _patched(RootNodeMulticlassClassification, "loss", loss):
        yield


@contextlib.contextmanager
def altered_answer():
    """Every served row is altered where the server produces it: its
    largest logit is raised by a tenth of the row's spread."""
    from repro.serve import gnn
    original = gnn.GNNServer.warmup

    def warmup(self, warmup_root=0):
        apply = self._apply

        def altered(params, graph):
            out = apply(params, graph)
            spread = out.max(-1, keepdims=True) - out.min(-1, keepdims=True)
            top = out == out.max(-1, keepdims=True)
            return out + 0.1 * spread * top
        self._apply = altered
        return original(self, warmup_root)

    with _patched(gnn.GNNServer, "warmup", warmup):
        yield


@contextlib.contextmanager
def no_topic_pool():
    """The VanillaMPNN's pool over ``has_topic`` comes back empty: its
    messages are computed and dropped, so no paper hears its fields."""
    from repro.core.convolutions import SimpleConv
    original = SimpleConv.__call__

    def call(self, params, graph, edge_set_name):
        out = original(self, params, graph, edge_set_name)
        return jnp.zeros_like(out) if edge_set_name == "has_topic" else out

    with _patched(SimpleConv, "__call__", call):
        yield


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer, "no_topic_pool": no_topic_pool}
