"""Launch-layer tests: rules, specs, serve engine, optim, checkpoint, hlo_cost."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro import optim
from repro.configs import ARCHS, SHAPES, FederatedConfig, reduced
from repro.launch import specs as specs_mod
from repro.launch.hlo_cost import hlo_cost, parse_hlo
from repro.launch.mesh import make_test_mesh
from repro.launch.rules import count_params, is_giant, make_rules, safe_pspec
from repro.launch.serve import ServeEngine
from repro.models.transformer import DecoderLM


class TestRules:
    def test_giant_classification(self):
        sizes = {}
        for name in ("command-r-plus-104b", "llama4-maverick-400b-a17b", "gemma-2b",
                     "mamba2-2.7b"):
            cfg = ARCHS[name]
            model = DecoderLM(cfg, dtype=jnp.bfloat16)
            sizes[name] = count_params(model)
        assert is_giant(ARCHS["command-r-plus-104b"], sizes["command-r-plus-104b"])
        assert is_giant(ARCHS["llama4-maverick-400b-a17b"],
                        sizes["llama4-maverick-400b-a17b"])
        assert not is_giant(ARCHS["gemma-2b"], sizes["gemma-2b"])
        # assigned sizes are in the right ballpark
        assert 90e9 < sizes["command-r-plus-104b"] < 120e9
        # the assignment pins MoE-128e in EVERY layer (Maverick itself
        # interleaves MoE/dense); the literal config is ~780B total, ~17B active
        assert 600e9 < sizes["llama4-maverick-400b-a17b"] < 900e9
        assert 2e9 < sizes["mamba2-2.7b"] < 3.5e9

    def test_param_counts_all_archs(self):
        """Every full config's parameter count is within its nameplate band."""
        from repro.models.encdec import EncDecLM
        bands = {
            "gemma-2b": (2.0e9, 3.2e9),
            "h2o-danube-3-4b": (3.0e9, 4.5e9),
            "granite-8b": (7e9, 9e9),
            "granite-moe-1b-a400m": (0.8e9, 1.6e9),
            "zamba2-2.7b": (2.2e9, 3.5e9),
            "chameleon-34b": (30e9, 38e9),
            "whisper-large-v3": (1.2e9, 2.2e9),
        }
        for name, (lo, hi) in bands.items():
            cfg = ARCHS[name]
            model = (EncDecLM if cfg.arch_type == "audio" else DecoderLM)(cfg, dtype=jnp.bfloat16)
            n = count_params(model)
            assert lo < n < hi, (name, n)

    def test_make_rules_modes(self):
        mesh = make_test_mesh()
        cfg = ARCHS["gemma-2b"]
        r_train = make_rules(cfg, mesh, mode="train", num_params=2.5e9)
        assert r_train["clients"] == "data"
        r_serve = make_rules(cfg, mesh, mode="serve", num_params=2.5e9)
        assert r_serve["clients"] is None and r_serve["batch"] == "data"
        r_giant = make_rules(cfg, mesh, mode="train", num_params=1e11)
        assert r_giant["clients"] is None and r_giant["embed"] == "data"


class TestSpecs:
    def test_train_specs_shapes(self):
        mesh = make_test_mesh()
        cfg = ARCHS["gemma-2b"]
        fed = FederatedConfig(local_steps=2)
        rules = make_rules(cfg, mesh, mode="train", num_params=2.5e9)
        shapes, logical = specs_mod.train_input_specs(cfg, SHAPES["train_4k"], fed, mesh, rules)
        k = specs_mod.cohort_size(mesh, rules)
        assert shapes["tokens"].shape == (k, 2, 256 // k, 4096)
        assert shapes["tokens"].dtype == jnp.int32

    def test_decode_specs_cache(self):
        mesh = make_test_mesh()
        cfg = reduced(ARCHS["mamba2-2.7b"])
        model = DecoderLM(cfg)
        rules = make_rules(cfg, mesh, mode="serve", num_params=1e8)
        shapes, logical = specs_mod.decode_input_specs(
            cfg, SHAPES["decode_32k"], mesh, rules, model)
        assert shapes["token"].shape == (128,)
        caches = shapes["caches"]["blocks"]
        assert caches["state"].shape[0] == cfg.num_layers


class TestServeEngine:
    def test_greedy_generate(self):
        cfg = reduced(ARCHS["granite-8b"], d_model=128)
        model = DecoderLM(cfg, attn_impl="dense", remat=False)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServeEngine(model)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
        out = engine.generate(params, prompt, max_new=5, cache_len=16, dtype=jnp.float32)
        assert out.shape == (2, 5)
        assert out.dtype == jnp.int32
        assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < cfg.vocab_size))

    def test_generate_deterministic(self):
        cfg = reduced(ARCHS["granite-8b"], d_model=128)
        model = DecoderLM(cfg, attn_impl="dense", remat=False)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServeEngine(model)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
        o1 = engine.generate(params, prompt, max_new=4, cache_len=16, dtype=jnp.float32)
        o2 = engine.generate(params, prompt, max_new=4, cache_len=16, dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


class TestOptim:
    def test_sgd_identity(self):
        opt = optim.sgd(1.0)
        g = {"w": jnp.ones(3)}
        step, _ = opt.update(g, opt.init(g))
        np.testing.assert_array_equal(np.asarray(step["w"]), np.ones(3))

    def test_adam_step_bounded(self):
        opt = optim.adam(lr=0.1)
        g = {"w": 100.0 * jnp.ones(4)}
        state = opt.init(g)
        step, state = opt.update(g, state)
        # adam normalizes: |step| ~ lr regardless of gradient scale
        assert np.all(np.abs(np.asarray(step["w"])) < 0.2)

    def test_momentum_accumulates(self):
        opt = optim.momentum(lr=1.0, beta=0.5)
        g = {"w": jnp.ones(2)}
        state = opt.init(g)
        s1, state = opt.update(g, state)
        s2, state = opt.update(g, state)
        assert float(s2["w"][0]) > float(s1["w"][0])

    def test_apply_update_dtype_preserved(self):
        p = {"w": jnp.ones(2, jnp.bfloat16)}
        out = optim.apply_update(p, {"w": jnp.ones(2, jnp.float32)})
        assert out["w"].dtype == jnp.bfloat16


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        cfg = reduced(ARCHS["gemma-2b"], d_model=128)
        model = DecoderLM(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        d = str(tmp_path)
        ckpt.save_checkpoint(d, 7, params, extra={"eta": 1.5})
        restored, meta = ckpt.load_checkpoint(d, params)
        assert meta["step"] == 7 and meta["eta"] == 1.5
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_step(self, tmp_path):
        d = str(tmp_path)
        assert ckpt.latest_step(d) is None
        ckpt.save_checkpoint(d, 3, {"w": jnp.ones(2)})
        ckpt.save_checkpoint(d, 11, {"w": jnp.ones(2)})
        assert ckpt.latest_step(d) == 11


class TestCompileCache:
    @pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
    def test_cache_dir(self, monkeypatch, env):
        """The environment's directory wins and nothing is set; otherwise the
        cache goes to the fixed, gitignored ``.jax_cache`` in the checkout.
        ``jax.config.update`` is recorded, not called: tests keep no cache."""
        from repro.launch import compile_cache

        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        updates = []
        monkeypatch.setattr(compile_cache.jax.config, "update",
                            lambda *a: updates.append(a))
        used = compile_cache.use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if env is None:
            assert used == os.path.join(root, ".jax_cache")
            assert updates == [("jax_compilation_cache_dir", used)]
        else:
            assert used == env and updates == []


class TestHloCost:
    def test_matmul_flops(self):
        """jit a plain matmul; the walker should count 2*m*n*k flops."""
        m, k, n = 64, 32, 48
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((m, k))
        b = jnp.ones((k, n))
        txt = f.lower(a, b).compile().as_text()
        c = hlo_cost(txt)
        assert c["flops"] == 2 * m * n * k

    def test_loop_multiplication(self):
        """fori_loop body flops are multiplied by the trip count."""
        m = 32
        trip = 7

        def body(x):
            return jax.lax.fori_loop(0, trip, lambda i, h: h @ h, x)

        x = jnp.eye(m)
        txt = jax.jit(body).lower(x).compile().as_text()
        c = hlo_cost(txt)
        assert c["flops"] == trip * 2 * m**3
        assert c["unknown_loops"] == 0

    def test_scan_layers(self):
        """lax.scan over stacked layer params multiplies like the layer count."""
        layers, d = 5, 16
        ws = jnp.stack([jnp.eye(d)] * layers)

        def f(x, ws):
            def step(h, w):
                return h @ w, None
            h, _ = jax.lax.scan(step, x, ws)
            return h

        txt = jax.jit(f).lower(jnp.ones((d, d)), ws).compile().as_text()
        c = hlo_cost(txt)
        assert c["flops"] == layers * 2 * d**3

    def test_parse_structure(self):
        txt = jax.jit(lambda a: a @ a).lower(jnp.ones((8, 8))).compile().as_text()
        comps, entry = parse_hlo(txt)
        assert entry is not None
        assert entry in comps
