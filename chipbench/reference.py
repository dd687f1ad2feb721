"""Plain reference of one DP-FedEXP run: the paper's Algorithms 1-3 in jnp.

Written from the paper (arXiv 2504.09850, Algorithms 1-3 and Eq. 8) and
the configuration file alone; it imports nothing of the program.  Every
client runs ``tau`` full-batch gradient steps on its loss (the family's
``ref_loss``) from the broadcast weights; the server clips each update to C,
releases the mean with Gaussian noise, picks the global step eta by the
mechanism's debiased FedEXP rule, floored at 1, and applies ``w + eta *
mean``.  After each round the held-out loss (the family's
``ref_test_loss``) is evaluated.

The release is the central-DP one (clip, mean, Gaussian noise on the mean,
Eq. 8's noised numerator).  Its noise is drawn from the run key as the
program documents its RNG streams, so that one run of each can be compared
round by round: round t uses ``fold_in(key, t)``, split in two, the first
for the (d,) noise on the mean (std sigma / sqrt(M)), the second for the
scalar numerator noise xi (std d sigma^2 / M); the (d,) vector is laid out
in the order ``jax.flatten_util.ravel_pytree`` gives the weight pytree.

Clients are trained in blocks of the family's ``ref_block`` (``lax.map``),
so the reference fits beside nothing else on the chip.  ``precision`` is
the matmul and convolution precision and ``dtype`` the type of the whole
computation: float32 at ``highest`` is the reference; bfloat16 at
``default`` is the control that ``correct`` has to refuse.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from chipbench import cell


class Spec:
    """A configuration as a static argument of ``jit``: equal, and hashed,
    by its JSON text, so that every run of one configuration shares the
    compiled round."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.text = json.dumps(cfg, sort_keys=True)

    def __hash__(self) -> int:
        return hash(self.text)

    def __eq__(self, other) -> bool:
        return isinstance(other, Spec) and other.text == self.text


def local_update(params, batch, *, loss, tau: int, eta_l):
    """tau full-batch GD steps on one client; returns w_tau - w."""
    def step(w, _):
        g = jax.grad(loss)(w, batch)
        return jax.tree_util.tree_map(lambda a, b: a - eta_l * b, w, g), None

    w_tau, _ = jax.lax.scan(step, params, None, length=tau)
    return jax.tree_util.tree_map(jnp.subtract, w_tau, params)


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        tree)


@functools.partial(jax.jit, static_argnames=("spec", "tau", "block", "dtype"))
def _round(w, batches, key, *, spec, tau, eta_l, clip, sigma, block, dtype):
    """One server round from ``w``: (w_next, per-round readings)."""
    family = cell.family(spec.cfg)
    loss = functools.partial(family.ref_loss, cfg=spec.cfg)
    flat0, unravel = ravel_pytree(w)
    d = flat0.shape[0]
    m = jax.tree_util.tree_leaves(batches)[0].shape[0]
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((m // block, block) + a.shape[1:]), batches)
    k_mech, k_xi = jax.random.split(key)

    def release(blk):
        deltas = jax.vmap(lambda b: local_update(
            w, b, loss=loss, tau=tau, eta_l=eta_l))(blk)
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


@functools.partial(jax.jit, static_argnames=("spec",))
def test_loss(w, test, *, spec):
    """The family's loss over the held-out set."""
    return cell.family(spec.cfg).ref_test_loss(w, test, spec.cfg)


def run(cfg: dict, w0, batches: dict, test: dict, key, *, rounds: int,
        precision: str = "highest", dtype=jnp.float32) -> dict:
    """Follow ``rounds`` rounds from ``w0`` with the run key ``key``.

    Returns per-round readings (lists of floats), the held-out loss before
    the first round and after each, and the average of the last
    ``avg_last`` iterates (what the server hands back).
    """
    if cfg["mechanism"] != "cdp":
        raise ValueError(f"the reference releases central DP only, not "
                         f"{cfg['mechanism']!r}")
    spec = Spec(cfg)
    block = cell.family(cfg).ref_block(cfg)
    w = _cast(w0, dtype)
    batches, test = _cast(batches, dtype), _cast(test, dtype)
    history: dict[str, list[float]] = {}
    iterates = [w]
    with jax.default_matmul_precision(precision):
        loss0 = float(test_loss(w, test, spec=spec))
        for t in range(rounds):
            w, readings = _round(
                w, batches, jax.random.fold_in(key, t), spec=spec,
                tau=cfg["tau"], eta_l=jnp.asarray(cfg["eta_l"], dtype),
                clip=jnp.asarray(cfg["clip"], dtype),
                sigma=jnp.asarray(cfg["sigma"], dtype), block=block, dtype=dtype)
            readings["loss"] = test_loss(w, test, spec=spec)
            for name, value in jax.device_get(readings).items():
                history.setdefault(name, []).append(float(value))
            iterates = (iterates + [w])[-cfg["avg_last"]:]
    f32 = functools.partial(_cast, dtype=jnp.float32)
    final = jax.tree_util.tree_map(lambda *a: sum(a) / len(a), *map(f32, iterates))
    return {"history": history, "loss0": loss0, "final_w": jax.device_get(final)}
