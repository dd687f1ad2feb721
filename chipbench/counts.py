"""Operations and bytes of a round, counted from the configuration's shapes.

Model FLOPs count what the algorithm needs, not what the program runs: the
forward and backward passes of every local step over every unmasked sample,
and the forward pass of the test set that the per-round eval makes.  The
forward count of one image is XLA's own count of the CNN's forward pass
(2 FLOPs a multiply-add, one a bias add, one a ReLU); the backward pass adds
the weight gradients of every layer, the input gradients of every layer but
the first (the images need none), the bias gradients and the ReLU masks.
Padding, recompute and the server's O(M d) release are not model FLOPs.
"""
from __future__ import annotations

import math


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


def params(model: dict) -> int:
    return sum(math.prod(l["w"]) + l["w"][-1] for l in model["layers"])
