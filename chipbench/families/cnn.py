"""The paper's MNIST CNNs (arXiv 2504.09850, Appendix E, Table 3).

Inputs: the benchmark's own copies of the generators the E2 experiment uses
(the generated 28x28 MNIST substitute, the label-Dirichlet client split and
the He-initialised CNN weights), so that a change to the program cannot move
the inputs it is measured on.  Every seed gives the same sizes (M clients of
``train_images // M`` images each).

Reference: the CNN's forward pass and masked cross-entropy, written from the
configuration's layer list.  Counts: model FLOPs of a round from the same
list.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.inputs import stream_keys

# -- the generated MNIST substitute (28x28, 10 classes) ---------------------

def _smooth_random_field(key, n: int, size: int = 28, cutoff: int = 6):
    """n low-frequency random images via truncated 2-D Fourier synthesis."""
    k_re, k_im = jax.random.split(key)
    coef = (jax.random.normal(k_re, (n, cutoff, cutoff))
            + 1j * jax.random.normal(k_im, (n, cutoff, cutoff)))
    spec = jnp.zeros((n, size, size), jnp.complex64).at[:, :cutoff, :cutoff].set(coef)
    img = jnp.real(jnp.fft.ifft2(spec)) * size
    img = img - img.min(axis=(1, 2), keepdims=True)
    return img / jnp.maximum(img.max(axis=(1, 2), keepdims=True), 1e-6)


def _make_split(key, templates, n: int, noise: float, shift_px: int):
    """n samples: a class template, shifted, gained, noised, clipped to [0, 1]."""
    k_lab, k_shift, k_noise, k_gain = jax.random.split(key, 4)
    labels = jax.random.randint(k_lab, (n,), 0, templates.shape[0])
    imgs = templates[labels]
    shifts = jax.random.randint(k_shift, (n, 2), -shift_px, shift_px + 1)
    imgs = jax.vmap(lambda im, s: jnp.roll(im, (s[0], s[1]), axis=(0, 1)))(imgs, shifts)
    gain = 0.8 + 0.4 * jax.random.uniform(k_gain, (n, 1, 1))
    imgs = jnp.clip(imgs * gain + noise * jax.random.normal(k_noise, imgs.shape), 0.0, 1.0)
    return imgs[..., None], labels.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_train", "num_test", "classes"))
def image_dataset(key, *, num_train: int, num_test: int, classes: int):
    """(train_x, train_y, test_x, test_y) on the device, in one call."""
    k_tpl, k_tr, k_te = jax.random.split(key, 3)
    templates = _smooth_random_field(k_tpl, classes)
    train_x, train_y = _make_split(k_tr, templates, num_train, 0.15, 2)
    test_x, test_y = _make_split(k_te, templates, num_test, 0.15, 2)
    return train_x, train_y, test_x, test_y


# -- label-Dirichlet client split (Hsu, Qi, Brown 2019) ---------------------

def dirichlet_indices(seed: int, labels: np.ndarray, clients: int,
                      alpha: float, per_client: int) -> np.ndarray:
    """(clients, per_client) sample indices; client i's class shares are
    drawn from Dir(alpha), its samples from each class pool.

    Clients are numbered in order of their largest class.  The algorithm
    treats the cohort as a set, so the order changes nothing but the order
    of sums; it makes each contiguous part of the cohort a different mix of
    classes, so that a reduction that leaves out part of the cohort moves
    the mean update far beyond rounding.
    """
    rng = np.random.default_rng(seed)
    labels = np.asarray(labels)
    classes = int(labels.max()) + 1
    by_class = [np.flatnonzero(labels == c) for c in range(classes)]
    props = rng.dirichlet(alpha * np.ones(classes), size=clients)
    idx = np.empty((clients, per_client), np.int32)
    largest = np.empty(clients, np.int32)
    for i in range(clients):
        counts = rng.multinomial(per_client, props[i])
        largest[i] = np.argmax(counts)
        idx[i] = np.concatenate([
            rng.choice(by_class[c], size=k, replace=k > len(by_class[c]))
            for c, k in enumerate(counts) if k])
    return idx[np.argsort(largest, kind="stable")]


# -- the CNN's weights ---------------------------------------------------------

def param_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, from the configuration's layer list."""
    shapes = {}
    for layer in model["layers"]:
        shapes[layer["name"] + "_w"] = tuple(layer["w"])
        shapes[layer["name"] + "_b"] = (layer["w"][-1],)
    return shapes


def fan_in(shape: tuple[int, ...]) -> int:
    return math.prod(shape[:-1])


def init_params(key, model: dict) -> dict[str, jax.Array]:
    """He-normal weights, zero biases, float32, in one jitted call."""
    shapes = param_shapes(model)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return {name: (jax.random.normal(k, shape) * jnp.sqrt(2.0 / fan_in(shape))
                       if name.endswith("_w") else jnp.zeros(shape, jnp.float32))
                for k, (name, shape) in zip(keys, shapes.items())}

    return make(key)


def make_inputs(seed: int, cfg: dict) -> dict:
    """Client batches, test set, initial weights and the run-key stream.

    As in the paper's E2 protocol the image set is one for every seed (its
    own fixed key); the seed draws the client split, the weights and the
    run keys.  A fixed test set is also what lets the compiled round
    program, which holds the test set the eval closes over, be read from
    the compile cache by a run with a new seed.
    """
    keys = stream_keys(seed)
    data = cfg["data"]
    train_x, train_y, test_x, test_y = image_dataset(
        jax.random.PRNGKey(data["dataset_seed"]), num_train=data["train_images"],
        num_test=data["test_images"], classes=data["classes"])
    clients = cfg["clients"]
    idx = dirichlet_indices(seed, jax.device_get(train_y), clients,
                            data["dirichlet_alpha"],
                            data["train_images"] // clients)
    idx = jnp.asarray(idx)
    batches = {"x": train_x[idx], "y": train_y[idx],
               "mask": jnp.ones(idx.shape, jnp.float32)}
    return {"batches": batches, "test": {"x": test_x, "y": test_y},
            "w0": init_params(keys["weights"], cfg["model"]),
            "runs_key": keys["runs"]}


def program_loss(cfg: dict):
    """The program's loss on the CNN's weight dict (``repro.models.cnn``)."""
    from repro.models.cnn import pytree_xent_loss

    return pytree_xent_loss()


# -- the plain reference ---------------------------------------------------------

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


def ref_loss(params, batch, cfg: dict):
    return xent(params, batch, cfg["model"]["layers"])


def ref_test_loss(params, test, cfg: dict):
    """Mean cross-entropy over the test set, in chunks of up to 500 images."""
    layers = cfg["model"]["layers"]
    n = test["y"].shape[0]
    chunk = math.gcd(n, 500)
    parts = jax.tree_util.tree_map(
        lambda a: a.reshape((n // chunk, chunk) + a.shape[1:]), test)
    sums = jax.lax.map(lambda b: xent(params, b, layers) * chunk, parts)
    return (jnp.sum(sums.astype(jnp.float32)) / n)


def ref_block(cfg: dict) -> int:
    return math.gcd(250, cfg["clients"])


# -- counts from shapes ----------------------------------------------------------
#
# The forward count of one image is XLA's own count of the CNN's forward
# pass (2 FLOPs a multiply-add, one a bias add, one a ReLU); the backward
# pass adds the weight gradients of every layer, the input gradients of
# every layer but the first (the images need none), the bias gradients and
# the ReLU masks.

def layers(model: dict, image: list[int]) -> list[dict]:
    """Per layer: multiply-adds, output elements and whether a ReLU follows."""
    h, w, c = image
    out = []
    n = len(model["layers"])
    for i, layer in enumerate(model["layers"]):
        if layer["kind"] == "conv":
            kh, kw, cin, cout = layer["w"]
            s = layer["stride"]
            h, w, c = (h - kh) // s + 1, (w - kw) // s + 1, cout
            elems = h * w * c
            macs = elems * kh * kw * cin
            relu = True
        else:
            fan_in, fan_out = layer["w"]
            if fan_in != h * w * c:
                raise ValueError(f"layer {layer['name']}: fan-in {fan_in} "
                                 f"!= {h * w * c} inputs")
            h, w, c = 1, 1, fan_out
            elems, macs = fan_out, fan_in * fan_out
            relu = i < n - 1
        out.append({"name": layer["name"], "macs": macs, "elems": elems,
                    "relu": relu})
    return out


def forward_flops(model: dict, image: list[int]) -> int:
    """FLOPs of one image's forward pass to the logits."""
    return sum(2 * l["macs"] + l["elems"] + (l["elems"] if l["relu"] else 0)
               for l in layers(model, image))


def backward_flops(model: dict, image: list[int]) -> int:
    """FLOPs of one image's backward pass, no input gradient at layer 1."""
    ls = layers(model, image)
    return sum(2 * l["macs"] * (1 if i == 0 else 2) + l["elems"]
               + (l["elems"] if l["relu"] else 0) for i, l in enumerate(ls))


def round_flops(cfg: dict) -> int:
    """Model FLOPs of one round: local training plus the eval."""
    data, model = cfg["data"], cfg["model"]
    samples = (data["train_images"] // cfg["clients"]) * cfg["clients"]
    step = forward_flops(model, data["image"]) + backward_flops(model, data["image"])
    return (samples * cfg["tau"] * step
            + data["test_images"] * forward_flops(model, data["image"]))


def params(cfg: dict) -> int:
    return sum(math.prod(l["w"]) + l["w"][-1] for l in cfg["model"]["layers"])
