"""Core trainable layers (Dense, norms, embeddings, MLPs)."""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.nn.module import (
    Axes, Module, Param, lecun_normal, normal_init, ones_init, zeros_init)


class Linear(Module):
    """Clean Dense layer: y = x @ W (+ b)."""

    def __init__(self, in_dim: int, out_dim: int, *, use_bias: bool = True,
                 kernel_axes: Axes = (None, None), w_init=None,
                 name: str = "linear"):
        self.in_dim = in_dim
        self.out_dim = out_dim
        self.use_bias = use_bias
        self.kernel_axes = tuple(kernel_axes)
        self.w_init = w_init or lecun_normal()
        self.name = name

    def init(self, key):
        params = {
            "w": Param(self.w_init(key, (self.in_dim, self.out_dim)),
                       self.kernel_axes)
        }
        if self.use_bias:
            params["b"] = Param(jnp.zeros((self.out_dim,)),
                                (self.kernel_axes[-1],))
        return params

    def __call__(self, params, x):
        w = params["w"]
        y = jnp.matmul(x, w.astype(x.dtype))
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y


class RMSNorm(Module):
    def __init__(self, dim: int, *, eps: float = 1e-6, axis_name=None,
                 name: str = "rmsnorm"):
        self.dim = dim
        self.eps = eps
        self.axis_name = axis_name
        self.name = name

    def init(self, key):
        del key
        return {"scale": Param(jnp.ones((self.dim,)), (self.axis_name,))}

    def __call__(self, params, x):
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(dtype)


class LayerNorm(Module):
    def __init__(self, dim: int, *, eps: float = 1e-5, use_bias: bool = True,
                 axis_name=None, name: str = "layernorm"):
        self.dim = dim
        self.eps = eps
        self.use_bias = use_bias
        self.axis_name = axis_name
        self.name = name

    def init(self, key):
        del key
        p = {"scale": Param(jnp.ones((self.dim,)), (self.axis_name,))}
        if self.use_bias:
            p["bias"] = Param(jnp.zeros((self.dim,)), (self.axis_name,))
        return p

    def __call__(self, params, x):
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"].astype(jnp.float32)
        if self.use_bias:
            y = y + params["bias"].astype(jnp.float32)
        return y.astype(dtype)


class Embedding(Module):
    """Token embedding with optional logit head reuse (tied weights)."""

    def __init__(self, vocab_size: int, dim: int, *,
                 axes: Axes = ("vocab", "embed"), name: str = "embed"):
        self.vocab_size = vocab_size
        self.dim = dim
        self._axes = tuple(axes)
        self.name = name

    def init(self, key):
        return {
            "table": Param(normal_init(0.02)(key, (self.vocab_size, self.dim)),
                           self._axes)
        }

    def __call__(self, params, ids: jnp.ndarray, dtype=jnp.bfloat16):
        return jnp.take(params["table"].astype(dtype), ids, axis=0)

    def attend(self, params, x):
        """Logits against the embedding table (tied softmax head)."""
        return jnp.matmul(x, params["table"].astype(x.dtype).T)


# Eigen's rational approximation of tanh on [-7.905, 7.905] (odd
# numerator, even denominator), which XLA's CPU backend also emits: a few
# float32 ulp from the true tanh in multiply-adds and one division.
_TANH_NUM = (4.89352455891786e-03, 6.37261928875436e-04,
             1.48572235717979e-05, 5.12229709037114e-08,
             -8.60467152213735e-11, 2.00018790482477e-13,
             -2.76076847742355e-16)
_TANH_DEN = (4.89352518554385e-03, 2.26843463243900e-03,
             1.18534705686654e-04, 1.19825839466702e-06)


@jax.custom_jvp
def rational_tanh(x):
    """tanh to float32 accuracy on every backend.  The float32 tanh of
    a TPU v5e errs by up to 8e-5 relative (this one: 3e-7), more than a
    float32 model whose states all pass through tanh can afford."""
    c = jnp.clip(x, -7.90531110763549805, 7.90531110763549805)
    c2 = c * c
    num = jnp.asarray(_TANH_NUM[-1], x.dtype)
    for a in _TANH_NUM[-2::-1]:
        num = num * c2 + jnp.asarray(a, x.dtype)
    den = jnp.asarray(_TANH_DEN[-1], x.dtype)
    for b in _TANH_DEN[-2::-1]:
        den = den * c2 + jnp.asarray(b, x.dtype)
    return jnp.where(jnp.abs(x) < 0.0004, x, c * num / den)


@rational_tanh.defjvp
def _rational_tanh_jvp(primals, tangents):
    y = rational_tanh(primals[0])
    return y, tangents[0] * (1 - y * y)


def rational_sigmoid(x):
    """sigmoid(x) = (1 + tanh(x / 2)) / 2, through `rational_tanh`."""
    return 0.5 + 0.5 * rational_tanh(0.5 * x)


ACTIVATIONS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "tanh": jnp.tanh,
    "identity": lambda x: x,
    "sigmoid": jax.nn.sigmoid,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


class MLP(Module):
    """Transformer FFN; gated (SwiGLU-family) or plain."""

    def __init__(self, dim: int, hidden: int, *, activation: str = "silu",
                 gated: bool = True, use_bias: bool = False,
                 name: str = "mlp"):
        self.dim = dim
        self.hidden = hidden
        self.act = ACTIVATIONS[activation]
        self.gated = gated
        self.use_bias = use_bias
        self.wi = Linear(dim, hidden, use_bias=use_bias,
                         kernel_axes=("embed", "mlp"))
        self.wg = Linear(dim, hidden, use_bias=use_bias,
                         kernel_axes=("embed", "mlp")) if gated else None
        self.wo = Linear(hidden, dim, use_bias=use_bias,
                         kernel_axes=("mlp", "embed"))
        self.name = name

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        p = {"wi": self.wi.init(k1), "wo": self.wo.init(k3)}
        if self.gated:
            p["wg"] = self.wg.init(k2)
        return p

    def __call__(self, params, x):
        h = self.wi(params["wi"], x)
        if self.gated:
            h = self.act(self.wg(params["wg"], x)) * h
        else:
            h = self.act(h)
        return self.wo(params["wo"], h)


class Dropout:
    """Functional dropout: caller supplies the rng (or None to disable)."""

    def __init__(self, rate: float):
        self.rate = rate

    def __call__(self, x, rng=None):
        if rng is None or self.rate <= 0.0:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
