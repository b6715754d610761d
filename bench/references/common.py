"""Plain pieces shared by the references: merging rooted subgraphs,
segment pools, the root-classification loss and AdamW.

A reference imports nothing of the program.  It is given the sampled
subgraphs as plain arrays (per graph: each node set's features, each
edge set's local source and target indices, the root first among the
papers), the weights as a nested dict of arrays, and the configuration.
It merges the graphs by its own code, without the program's padding:
node and edge counts are rounded up to a bucket only so that steps of
different sizes share a compiled program, and the rounded-up rows are
masked out of every pool.
"""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 4096


def _round_up(n: int) -> int:
    return max(BUCKET, int(math.ceil(n / BUCKET)) * BUCKET)


def merge(graphs: list, schema_edges: dict) -> dict:
    """One batch from rooted subgraphs.  ``schema_edges`` maps each edge
    set to its (source, target) node sets."""
    node_sets = sorted({ns for g in graphs for ns in g["nodes"]})
    counts = {ns: np.asarray([g["nodes"].get(ns, {}).get("n", 0)
                              for g in graphs]) for ns in node_sets}
    offsets = {ns: np.concatenate([[0], np.cumsum(c)[:-1]])
               for ns, c in counts.items()}
    batch = {"nodes": {}, "edges": {}}
    for ns in node_sets:
        total = int(counts[ns].sum())
        cap = _round_up(total + 1)
        feats = {}
        for g in graphs:
            for k, v in g["nodes"].get(ns, {}).items():
                if k != "n":
                    feats.setdefault(k, []).append(np.asarray(v))
        batch["nodes"][ns] = {}
        for k, parts in feats.items():
            arr = np.concatenate(parts)
            pad = np.zeros((cap - len(arr),) + arr.shape[1:], arr.dtype)
            batch["nodes"][ns][k] = np.concatenate([arr, pad])
    for es, (src_ns, tgt_ns) in sorted(schema_edges.items()):
        srcs, tgts = [], []
        for i, g in enumerate(graphs):
            if es in g["edges"]:
                s, t = g["edges"][es]
                srcs.append(np.asarray(s, np.int64) + offsets[src_ns][i])
                tgts.append(np.asarray(t, np.int64) + offsets[tgt_ns][i])
        if not srcs or src_ns not in counts or tgt_ns not in counts:
            continue
        src, tgt = np.concatenate(srcs), np.concatenate(tgts)
        cap = _round_up(len(src) + 1)
        valid = np.zeros(cap, bool)
        valid[:len(src)] = True
        batch["edges"][es] = {
            "src": np.pad(src, (0, cap - len(src))).astype(np.int32),
            "tgt": np.pad(tgt, (0, cap - len(tgt))).astype(np.int32),
            "valid": valid}
    batch["roots"] = offsets["paper"].astype(np.int32)
    return batch


def device_put(tree, device):
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, device), tree)


def gather(h, idx):
    return jnp.take(h, idx, axis=0)


def segment_sum(values, edge: dict, n: int, at: str = "tgt"):
    """Sum of per-edge values at each edge's target (``at="src"``: its
    source); masked rows drop."""
    ids = jnp.where(edge["valid"], edge[at], n)
    return jax.ops.segment_sum(values, ids, num_segments=n)


def segment_mean(values, edge: dict, n: int):
    """Mean of per-edge values at each edge's target."""
    total = segment_sum(values, edge, n)
    count = segment_sum(jnp.ones((values.shape[0], 1), values.dtype),
                        edge, n)
    return total / jnp.maximum(count, 1)


def _split(x):
    """x as a bfloat16 high part and a bfloat16 remainder, in float32."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _dot3(a, b):
    """A matmul of three bfloat16 passes (hi*hi + hi*lo + lo*hi), what a
    TPU computes at 'high' precision, with exact float32 products."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return ah @ bh + (ah @ bl + al @ bh)


@jax.custom_vjp
def _mm_high(a, b):
    return _dot3(a, b)


def _mm_high_fwd(a, b):
    return _dot3(a, b), (a, b)


def _mm_high_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


_mm_high.defvjp(_mm_high_fwd, _mm_high_bwd)


def mm(x, w, precision: str):
    """x @ w in float32: exact at 'highest' (the reference), three
    bfloat16 passes forward and backward at 'high' (the control)."""
    if precision == "highest":
        return x @ w
    if precision == "high":
        return _mm_high(x, w)
    raise ValueError(f"no reference matmul at precision {precision!r}")


def linear(p, x, precision: str):
    y = mm(x, p["w"], precision)
    return y + p["b"] if "b" in p else y


def layer_norm(p, x, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


def learning_rate(opt: dict, step: int) -> float:
    """Warmup then cosine decay to ``final_frac`` of the peak."""
    peak, warm, total = (opt["learning_rate"], opt["warmup_steps"],
                         opt["total_steps"])
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (opt["final_frac"] + (1 - opt["final_frac"]) * 0.5
                   * (1 + math.cos(math.pi * prog)))


def adamw(opt: dict):
    """(params, grads, m, v, step) -> (params, m, v, clipped grads): AdamW
    after clipping the gradient by its global norm."""

    @jax.jit
    def update(params, grads, m, v, step, lr):
        leaves = jax.tree_util.tree_leaves(grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, opt["max_grad_norm"] / (norm + 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        b1, b2 = opt["b1"], opt["b2"]
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                   m, grads)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                   v, grads)
        bc1 = 1 - b1 ** step
        bc2 = 1 - b2 ** step

        def upd(p, m, v):
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
            return p - lr * (delta + opt["weight_decay"] * p)

        return jax.tree_util.tree_map(upd, params, m, v), m, v, grads, norm

    return update


def train(loss_fn, params, batches: list, opt: dict, device):
    """The reference's steps over `batches`: (losses, clipped gradient of
    step 1, parameters after the last step), in float32 on `device`."""
    params = device_put(params, device)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    update = adamw(opt)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches, start=1):
            loss, grads = grad_fn(params, device_put(b, device))
            params, m, v, clipped, norm = update(
                params, grads, m, v, jnp.float32(i),
                jnp.float32(learning_rate(opt, i)))
            losses.append(float(loss))
            if first is None:
                first = clipped
                print(f"reference: step-1 gradient global norm "
                      f"{float(norm):.9g}", file=sys.stderr, flush=True)
    return losses, first, params


def logits(forward, params, batch: dict, device):
    """Root logits of one merged batch."""
    with jax.default_matmul_precision("highest"):
        out = jax.jit(forward)(device_put(params, device),
                               device_put(batch, device))
        return np.asarray(out, np.float32)
