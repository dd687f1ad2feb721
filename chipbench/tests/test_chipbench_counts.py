"""The benchmark's counts from shapes (the CNN family's, through
``counts.round_flops``), and its table of peaks (CPU)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import counts, peaks  # noqa: E402
from chipbench.families import cnn  # noqa: E402

CFG = json.loads((ROOT / "chipbench" / "configs" / "e2-cdp-cnn.json").read_text())


def test_forward_flops_equal_xla_cost_analysis_for_one_image():
    import jax
    import jax.numpy as jnp

    model, image = CFG["model"], CFG["data"]["image"]
    params = cnn.init_params(jax.random.PRNGKey(0), model)
    x = jnp.zeros((1, *image), jnp.float32)
    fwd = jax.jit(lambda p, x: cnn.forward(p, x, model["layers"]))
    cost = fwd.lower(params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cnn.forward_flops(model, image) == int(cost["flops"]) == 48_530


def test_round_flops_count_training_and_eval():
    model, image = CFG["model"], CFG["data"]["image"]
    # dW of all four layers, dX of all but conv1, bias grads, ReLU masks
    assert cnn.backward_flops(model, image) == 73_746
    per_round = 1000 * 12 * 10 * (48_530 + 73_746) + 2000 * 48_530
    assert counts.round_flops(CFG) == cnn.round_flops(CFG) == per_round


def test_params_match_the_program_cnn():
    import jax

    from repro.models.cnn import make_cnn_params

    program = make_cnn_params(jax.random.PRNGKey(0), CFG["model"]["variant"])
    ours = {f"{l['name']}_w": tuple(l["w"]) for l in CFG["model"]["layers"]}
    assert {k: v.shape for k, v in program.items() if k.endswith("_w")} == ours
    assert cnn.params(CFG) == CFG["model"]["params"] == 5_046


def test_peaks_of_v5e_and_refusal_of_an_unknown_chip():
    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert p["source"].startswith("https://")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
