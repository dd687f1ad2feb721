"""Correctness of the §Perf variants: chunked attention, group-local MoE."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.kernels.flash_attention.ref import attention_ref
from repro.launch.mesh import make_test_mesh
from repro.models.attention import blockwise_attention, chunked_attention
from repro.models.moe import moe_apply, moe_defs
from repro.models.sharding import AXIS_SIZES_KEY, axis_rules
from repro.models.common import init_params


class TestChunkedAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("sq", [64, 96])
    def test_matches_ref(self, causal, sq):
        b, hq, hkv, dh = 2, 4, 2, 32
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, sq, hq, dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, sq, hkv, dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, sq, hkv, dh))
        got = chunked_attention(q, k, v, causal=causal, window=None, block_q=32)
        # ref takes (B, H, S, D)
        want = attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                             jnp.moveaxis(v, 2, 1), causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.moveaxis(want, 1, 2)),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_blockwise_with_window(self):
        b, h, s, dh = 1, 2, 128, 16
        key = jax.random.PRNGKey(3)
        q = jax.random.normal(key, (b, s, h, dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, dh))
        a = chunked_attention(q, k, v, causal=True, window=32, block_q=32)
        bw = blockwise_attention(q, k, v, causal=True, window=32, block_k=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(bw), rtol=2e-4, atol=2e-4)


class TestGroupLocalMoE:
    def _setup(self, e=4, k=2, d=64, f=128):
        cfg = dataclasses.replace(
            reduced(ARCHS["granite-moe-1b-a400m"], d_model=d),
            num_experts=e, top_k=k, d_ff=f, capacity_factor=8.0)
        defs = moe_defs(cfg)
        params = init_params(jax.random.PRNGKey(0), defs, jnp.float32)
        # router init is zeros-protected? router is 2D -> dense init; fine
        return cfg, params

    def test_grouped_matches_ungrouped(self):
        """g>1 dispatch == g=1 dispatch when capacity is drop-free."""
        cfg, params = self._setup()
        b, s = 4, 16
        x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model))
        y1, aux1 = moe_apply(params, x, cfg)  # no rules -> g=1
        # pretend sizes say 4 data shards (drives g=4); the real 1-device
        # mesh satisfies every constraint trivially, so this exercises the
        # grouped dispatch MATH against the ungrouped path.
        rules = {"batch": "data", AXIS_SIZES_KEY: {"data": 4, "model": 1}}
        mesh = make_test_mesh()
        with mesh, axis_rules(rules):
            y4, aux4 = jax.jit(lambda p, xx: moe_apply(p, xx, cfg))(params, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(aux1), float(aux4), rtol=1e-4)

    def test_capacity_drops_are_weighted_zero(self):
        cfg, params = self._setup()
        cfg = dataclasses.replace(cfg, capacity_factor=0.01)  # force drops
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
        y, _ = moe_apply(params, x, cfg)
        assert np.all(np.isfinite(np.asarray(y)))

    def test_group_fallback_small_batch(self):
        """b % g != 0 falls back to g=1 silently."""
        cfg, params = self._setup()
        x = jax.random.normal(jax.random.PRNGKey(3), (3, 8, cfg.d_model))
        rules = {"batch": "data", AXIS_SIZES_KEY: {"data": 2, "model": 1}}
        mesh = make_test_mesh()
        with mesh, axis_rules(rules):
            y, _ = jax.jit(lambda p, xx: moe_apply(p, xx, cfg))(params, x)
        assert y.shape == (3, 8, cfg.d_model)
