"""The paper's MNIST CNNs (Appendix E, Table 3) as flat-parameter models.

CDP model:  conv(4 filters, 4x4) -> conv(8, 4x4) -> FC 128->32 -> ReLU -> FC 32->10
LDP model:  conv(2, 4x4) -> conv(1, 4x4) -> FC 16->10

Strides are not stated in the paper; we use stride 2 then 3 (VALID), which is
the unique choice making the flatten widths equal the stated FC fan-ins
(28 -> 13 -> 4: 4*4*8 = 128 for CDP, 4*4*1 = 16 for LDP).  ReLU follows each
conv (the paper's table lists only the FC ReLU; a linear conv stack cannot
learn the task — deviation noted).  Softmax is folded into the cross-entropy.

Each convolution is written as its kh*kw strided slices of the input,
concatenated into patches, and one matmul against the kernel reshaped to
(kh*kw*c_in, c_out): the same sums as ``lax.conv_general_dilated``.  Under
the engine's ``vmap`` over clients with per-client weights, a convolution
becomes a grouped convolution whose weight gradient XLA lowers to a 3-D
convolution dilated along the client axis, far from the chip's matmul unit;
the patch matmul becomes a batched matmul with the client as batch, the form
the FC layers already take.

Parameter counts: CDP d = 5,046; LDP d = 237 — small enough that LDP noise
O(d sigma^2) stays informative, matching the paper's LDP/CDP model split.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.fedsim.flat import flatten_model

__all__ = ["CNNModel", "make_cnn", "make_cnn_params", "masked_xent_loss",
           "pytree_xent_loss", "accuracy_fn", "pytree_accuracy_fn"]


def _conv(x, w, b, stride):
    """VALID ``stride``-strided convolution, NHWC input and HWIO kernel: one
    (n*oh*ow, kh*kw*c_in) x (kh*kw*c_in, c_out) matmul of strided patches."""
    n, h, wd, c_in = x.shape
    kh, kw, _, c_out = w.shape
    oh, ow = (h - kh) // stride + 1, (wd - kw) // stride + 1
    patches = jnp.concatenate(
        [jax.lax.slice(x, (0, i, j, 0),
                       (n, i + (oh - 1) * stride + 1, j + (ow - 1) * stride + 1, c_in),
                       (1, stride, stride, 1))
         for i in range(kh) for j in range(kw)], axis=-1)
    k = kh * kw * c_in
    y = patches.reshape(n * oh * ow, k) @ w.reshape(k, c_out)
    return y.reshape(n, oh, ow, c_out) + b


def _forward(params, x):
    h = jax.nn.relu(_conv(x, params["c1_w"], params["c1_b"], 2))
    h = jax.nn.relu(_conv(h, params["c2_w"], params["c2_b"], 3))
    h = h.reshape(h.shape[0], -1)
    if "f1_w" in params:
        h = jax.nn.relu(h @ params["f1_w"] + params["f1_b"])
    return h @ params["out_w"] + params["out_b"]


@dataclasses.dataclass
class CNNModel:
    init_flat: jax.Array
    unravel: Callable
    dim: int

    def apply(self, w_flat: jax.Array, x: jax.Array) -> jax.Array:
        return _forward(self.unravel(w_flat), x)


def make_cnn_params(key: jax.Array, variant: str = "cdp") -> dict:
    """The raw parameter PYTREE of the paper's CNNs (He-init convs + FCs).

    The pytree is a first-class model for the session API: pass it straight
    to ``FederatedSession`` with ``pytree_xent_loss()`` and the session
    ravels at the clip/aggregate boundary (DESIGN.md §10/§11).  ``make_cnn``
    wraps it into the historical flat-vector ``CNNModel``.
    """
    ks = jax.random.split(key, 6)
    he = lambda k, shape, fan_in: jax.random.normal(k, shape) * jnp.sqrt(2.0 / fan_in)
    if variant == "cdp":
        return {
            "c1_w": he(ks[0], (4, 4, 1, 4), 16), "c1_b": jnp.zeros(4),
            "c2_w": he(ks[1], (4, 4, 4, 8), 64), "c2_b": jnp.zeros(8),
            "f1_w": he(ks[2], (128, 32), 128), "f1_b": jnp.zeros(32),
            "out_w": he(ks[3], (32, 10), 32), "out_b": jnp.zeros(10),
        }
    if variant == "ldp":
        return {
            "c1_w": he(ks[0], (4, 4, 1, 2), 16), "c1_b": jnp.zeros(2),
            "c2_w": he(ks[1], (4, 4, 2, 1), 32), "c2_b": jnp.zeros(1),
            "out_w": he(ks[2], (16, 10), 16), "out_b": jnp.zeros(10),
        }
    raise ValueError(f"unknown CNN variant {variant!r}")


def make_cnn(key: jax.Array, variant: str = "cdp") -> CNNModel:
    """variant: 'cdp' (4/8 filters + hidden FC) or 'ldp' (2/1 filters)."""
    params = make_cnn_params(key, variant)
    flat, unravel = flatten_model(params)
    return CNNModel(init_flat=flat, unravel=unravel, dim=flat.shape[0])


def _masked_xent(logits, batch):
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    mask = batch.get("mask")
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def masked_xent_loss(model: CNNModel):
    """Client loss on the flat model: mask-weighted mean softmax xent."""

    def loss(w_flat, batch):
        return _masked_xent(model.apply(w_flat, batch["x"]), batch)

    return loss


def pytree_xent_loss():
    """Client loss on the raw parameter pytree (``make_cnn_params``) — what a
    ``LocalSpec`` minibatch session trains without any hand-written flat
    wrapper."""

    def loss(params, batch):
        return _masked_xent(_forward(params, batch["x"]), batch)

    return loss


def accuracy_fn(model: CNNModel, x: jax.Array, y: jax.Array, chunk: int = 1000):
    """Eval closure: test accuracy (Fig. 1 right metric)."""

    def fn(w_flat):
        n = x.shape[0]
        correct = 0.0
        for s in range(0, n, chunk):
            logits = model.apply(w_flat, jax.lax.dynamic_slice_in_dim(x, s, min(chunk, n - s)))
            correct += jnp.sum(jnp.argmax(logits, -1) == jax.lax.dynamic_slice_in_dim(y, s, min(chunk, n - s)))
        return correct / n

    return fn


def pytree_accuracy_fn(x: jax.Array, y: jax.Array, chunk: int = 1000):
    """``accuracy_fn`` for raw parameter pytrees (``make_cnn_params``)."""

    def fn(params):
        n = x.shape[0]
        correct = 0.0
        for s in range(0, n, chunk):
            logits = _forward(params, jax.lax.dynamic_slice_in_dim(x, s, min(chunk, n - s)))
            correct += jnp.sum(jnp.argmax(logits, -1) == jax.lax.dynamic_slice_in_dim(y, s, min(chunk, n - s)))
        return correct / n

    return fn
