"""The paper's CNNs (``repro.models.cnn``): the convolution written as patches
and one matmul.

``_conv`` must compute what ``lax.conv_general_dilated`` computes, values and
gradients, alone and vmapped over per-client weights as the engines run it;
and the round program a CNN session compiles must hold no convolution op, so
that under the engine's ``vmap`` every per-client contraction is a batched
matmul and none a grouped convolution.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.fedexp import make_algorithm
from repro.fedsim import EngineSpec, FederatedSession, StreamSpec, TrainSpec
from repro.models.cnn import (
    _conv,
    make_cnn_params,
    pytree_accuracy_fn,
    pytree_xent_loss,
)

# (kernel HWIO, stride, input side) of each conv of the two variants
CONVS = {
    "cdp_c1": ((4, 4, 1, 4), 2, 28),
    "cdp_c2": ((4, 4, 4, 8), 3, 13),
    "ldp_c1": ((4, 4, 1, 2), 2, 28),
    "ldp_c2": ((4, 4, 2, 1), 3, 13),
}
CLIENTS, IMAGES = 3, 5


def _reference(x, w, b, stride):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST) + b


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _value_and_grads(conv, x, w, b, stride, vmapped):
    """The conv's output and the gradients of a fixed random projection of
    it w.r.t. the kernel, bias and input; vmapped over a leading client axis
    of all three when ``vmapped``."""
    def f(x, w, b):
        y = conv(x, w, b, stride)
        proj = jnp.sin(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape)
        return jnp.sum(y * proj), y

    g = jax.grad(f, argnums=(0, 1, 2), has_aux=True)
    if vmapped:
        g = jax.vmap(g)
    (gx, gw, gb), y = g(x, w, b)
    return y, gw, gb, gx


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "vmapped"])
@pytest.mark.parametrize("name", sorted(CONVS))
def test_conv_matches_lax_conv(name, vmapped):
    wshape, stride, side = CONVS[name]
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 3)
    lead = (CLIENTS,) if vmapped else ()
    x = jax.random.normal(kx, lead + (IMAGES, side, side, wshape[2]))
    w = jax.random.normal(kw, lead + wshape)
    b = jax.random.normal(kb, lead + wshape[-1:])
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(_conv, x, w, b, stride, vmapped)
    want = _value_and_grads(_reference, x, w, b, stride, vmapped)
    for part, g, r in zip(("value", "d_kernel", "d_bias", "d_input"), got, want):
        assert g.shape == r.shape, part
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(r))),
                                   err_msg=part)


ENGINES = {
    "scan": {},
    "stream": dict(engine=EngineSpec(engine="stream"),
                   stream=StreamSpec(chunk_clients=8)),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_cnn_round_program_holds_no_convolution(engine):
    """A CDP CNN session of 16 clients of 12 images, tau = 2: its lowered and
    compiled round programs contract with dots, none with a convolution."""
    kx, ky, kp = jax.random.split(jax.random.PRNGKey(5), 3)
    batches = {"x": jax.random.normal(kx, (16, 12, 28, 28, 1)),
               "y": jax.random.randint(ky, (16, 12), 0, 10),
               "mask": jnp.ones((16, 12))}
    alg = make_algorithm("cdp-fedexp", clip_norm=1.0, sigma=0.1, num_clients=16)
    session = FederatedSession(
        alg, pytree_xent_loss(), make_cnn_params(kp, "cdp"), batches,
        train=TrainSpec(rounds=2, tau=2, eta_l=0.1),
        eval_fn=pytree_accuracy_fn(batches["x"][0], batches["y"][0]),
        **ENGINES[engine])
    lowered = session.lower(jax.random.PRNGKey(0))
    text = lowered.as_text()
    assert "dot_general" in text
    assert "convolution" not in text
    assert " convolution(" not in lowered.compile().as_text()
