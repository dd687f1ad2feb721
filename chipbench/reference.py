"""Plain reference of one DP-FedEXP run: the paper's Algorithms 1-3 in jnp.

Written from the paper (arXiv 2504.09850, Algorithms 1-3 and Eq. 8) and
the configuration file alone; it imports nothing of the program.  Every
client runs ``tau`` full-batch gradient steps on its masked mean
cross-entropy from the broadcast weights; the server clips each update to C,
releases the mean with Gaussian noise, picks the global step eta by the
mechanism's debiased FedEXP rule, floored at 1, and applies ``w + eta *
mean``.  After each round the test cross-entropy is evaluated.

The release is the central-DP one (clip, mean, Gaussian noise on the mean,
Eq. 8's noised numerator).  Its noise is drawn from the run key as the
program documents its RNG streams, so that one run of each can be compared
round by round: round t uses ``fold_in(key, t)``, split in two, the first
for the (d,) noise on the mean (std sigma / sqrt(M)), the second for the
scalar numerator noise xi (std d sigma^2 / M); the (d,) vector is laid out
in the order ``jax.flatten_util.ravel_pytree`` gives the weight dict.

Clients are trained in blocks (``lax.map``), so the reference fits beside
nothing else on the chip.  ``precision`` is the matmul and convolution
precision and ``dtype`` the type of the whole computation: float32 at
``highest`` is the reference; bfloat16 at ``default`` is the control that
``correct`` has to refuse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


def forward(params: dict, x, layers: list[dict]):
    """Logits of the configuration's CNN: VALID strided convolutions with
    ReLU, then dense layers with ReLU between them."""
    h = x
    for i, layer in enumerate(layers):
        w, b = params[layer["name"] + "_w"], params[layer["name"] + "_b"]
        if layer["kind"] == "conv":
            s = layer["stride"]
            h = jax.lax.conv_general_dilated(
                h, w, window_strides=(s, s), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            h = jax.nn.relu(h + b)
        else:
            h = h.reshape(h.shape[0], -1) @ w + b
            if i < len(layers) - 1:
                h = jax.nn.relu(h)
    return h


def xent(params, batch, layers):
    """Mean softmax cross-entropy, weighted by the batch's mask if it has one."""
    logp = jax.nn.log_softmax(forward(params, batch["x"], layers))
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    mask = batch.get("mask")
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def local_update(params, batch, *, layers, tau: int, eta_l):
    """tau full-batch GD steps on one client; returns w_tau - w."""
    def step(w, _):
        g = jax.grad(xent)(w, batch, layers)
        return jax.tree_util.tree_map(lambda a, b: a - eta_l * b, w, g), None

    w_tau, _ = jax.lax.scan(step, params, None, length=tau)
    return jax.tree_util.tree_map(jnp.subtract, w_tau, params)


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        tree)


@functools.partial(jax.jit, static_argnames=("layers", "tau", "block", "dtype"))
def _round(w, batches, key, *, layers, tau, eta_l, clip, sigma, block, dtype):
    """One server round from ``w``: (w_next, per-round readings)."""
    layers = [dict(layer) for layer in layers]
    flat0, unravel = ravel_pytree(w)
    d = flat0.shape[0]
    m = jax.tree_util.tree_leaves(batches)[0].shape[0]
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((m // block, block) + a.shape[1:]), batches)
    k_mech, k_xi = jax.random.split(key)

    def release(blk):
        deltas = jax.vmap(lambda b: local_update(
            w, b, layers=layers, tau=tau, eta_l=eta_l))(blk)
        flat = jax.vmap(lambda t: ravel_pytree(t)[0])(deltas)      # (B, d)
        sq = jnp.sum(flat * flat, axis=1)
        scale = jnp.minimum(1.0, clip / jnp.sqrt(jnp.maximum(sq, 1e-12)))
        clipped = flat * scale[:, None].astype(dtype)
        return jnp.sum(clipped, axis=0), jnp.sum(sq * scale * scale)

    sums, sq_clip = jax.lax.map(release, blocks)
    msc = jnp.sum(sq_clip) / m
    mean = jnp.sum(sums, axis=0) / m + (sigma / math.sqrt(m)) * jax.random.normal(
        k_mech, (d,)).astype(dtype)
    agg_sq = jnp.sum(mean * mean)
    xi = (d * sigma ** 2 / m) * jax.random.normal(k_xi, ()).astype(dtype)
    eta = jnp.maximum(1.0, (msc + xi) / jnp.maximum(agg_sq, 1e-12)).astype(dtype)
    w_next = unravel((flat0 + eta * mean).astype(dtype))
    readings = {"eta": eta, "eta_target": msc / jnp.maximum(agg_sq, 1e-12),
                "mean_sq_clipped": msc, "agg_sq": agg_sq}
    return w_next, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                          readings)


@functools.partial(jax.jit, static_argnames=("layers",))
def test_loss(w, test, *, layers):
    """Mean cross-entropy over the test set, in chunks of up to 500 images."""
    layers = [dict(layer) for layer in layers]
    n = test["y"].shape[0]
    chunk = math.gcd(n, 500)
    parts = jax.tree_util.tree_map(
        lambda a: a.reshape((n // chunk, chunk) + a.shape[1:]), test)
    sums = jax.lax.map(lambda b: xent(w, b, layers) * chunk, parts)
    return (jnp.sum(sums.astype(jnp.float32)) / n)


def hashable_layers(layers: list[dict]) -> tuple:
    return tuple(tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                              for k, v in layer.items())) for layer in layers)


def run(cfg: dict, w0: dict, batches: dict, test: dict, key, *, rounds: int,
        precision: str = "highest", dtype=jnp.float32, block: int = 250) -> dict:
    """Follow ``rounds`` rounds from ``w0`` with the run key ``key``.

    Returns per-round readings (lists of floats), the test loss before
    the first round and after each, and the average of the last
    ``avg_last`` iterates (what the server hands back).
    """
    if cfg["mechanism"] != "cdp":
        raise ValueError(f"the reference releases central DP only, not "
                         f"{cfg['mechanism']!r}")
    layers = hashable_layers(cfg["model"]["layers"])
    w = _cast(w0, dtype)
    batches, test = _cast(batches, dtype), _cast(test, dtype)
    history: dict[str, list[float]] = {}
    iterates = [w]
    with jax.default_matmul_precision(precision):
        loss0 = float(test_loss(w, test, layers=layers))
        for t in range(rounds):
            w, readings = _round(
                w, batches, jax.random.fold_in(key, t), layers=layers,
                tau=cfg["tau"], eta_l=jnp.asarray(cfg["eta_l"], dtype),
                clip=jnp.asarray(cfg["clip"], dtype),
                sigma=jnp.asarray(cfg["sigma"], dtype),
                block=math.gcd(block, cfg["clients"]), dtype=dtype)
            readings["loss"] = test_loss(w, test, layers=layers)
            for name, value in jax.device_get(readings).items():
                history.setdefault(name, []).append(float(value))
            iterates = (iterates + [w])[-cfg["avg_last"]:]
    f32 = functools.partial(_cast, dtype=jnp.float32)
    final = jax.tree_util.tree_map(lambda *a: sum(a) / len(a), *map(f32, iterates))
    return {"history": history, "loss0": loss0, "final_w": jax.device_get(final)}
