"""End-to-end driver: DP-FedEXP federated training of a transformer LM.

This is the datacenter path (repro.launch) on real hardware-free CPU: the same
``train_step`` that the 512-chip dry-run lowers, executed eagerly on a small
cohort, with checkpointing and a token pipeline.

    PYTHONPATH=src python examples/train_federated_lm.py                 # ~12M params, quick
    PYTHONPATH=src python examples/train_federated_lm.py --d-model 768 \
        --layers 12 --rounds 200                                         # ~100M-class run

Synthetic token stream (offline container): each client draws from its own
Markov chain over the vocab so client data is genuinely heterogeneous — the
regime DP-FedEXP targets.
"""
import argparse
import sys

sys.path.insert(0, "src")

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import ARCHS, FederatedConfig, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.launch.rules import count_params
from repro.launch.train import FederatedTrainer
from repro.models.transformer import DecoderLM
from repro.telemetry import CompositeTracker, JsonlTracker, StdoutTracker


def make_client_stream(key, num_clients: int, vocab: int, *, order_states: int = 64):
    """Per-client Markov chains: shared backbone + client-specific transitions."""
    k1, k2 = jax.random.split(key)
    base = jax.random.dirichlet(k1, 0.5 * jnp.ones(vocab), (order_states,))
    biases = jax.random.dirichlet(k2, 0.3 * jnp.ones(vocab), (num_clients, order_states))
    trans = 0.5 * base[None] + 0.5 * biases          # (M, S, V)
    cum = jnp.cumsum(trans, axis=-1)

    def sample(key, client, tau, b, s):
        def tok_step(carry, k):
            state = carry
            u = jax.random.uniform(k, state.shape)
            row = cum[client, state % order_states]          # (..., V)
            nxt = jnp.argmax(u[..., None] <= row, axis=-1)
            return nxt.astype(jnp.int32), nxt.astype(jnp.int32)

        keys = jax.random.split(key, s)
        init = jnp.zeros((tau, b), jnp.int32)
        _, toks = jax.lax.scan(tok_step, init, keys)
        return jnp.moveaxis(toks, 0, -1)                      # (tau, b, s)

    return sample


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", help="family to reduce from")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--cohort", type=int, default=4)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--algorithm", default="cdp-fedexp")
    ap.add_argument("--ckpt-dir", default="results/ckpt_lm")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="also stream per-round JSONL telemetry to PATH")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        reduced(ARCHS[args.arch], layers=args.layers, d_model=args.d_model),
        vocab_size=args.vocab)
    model = DecoderLM(cfg, attn_impl="xla_flash", remat=False)
    fed = FederatedConfig(algorithm=args.algorithm, local_steps=args.tau,
                          local_lr=0.05, clip_norm=1.0, noise_sigma=0.05)
    n = count_params(model)
    print(f"model: {cfg.name} d={args.d_model} L={args.layers} vocab={args.vocab} "
          f"-> {n/1e6:.1f}M params; algorithm={args.algorithm}")

    trainer = FederatedTrainer(model, fed, n)
    step = jax.jit(trainer.make_train_step(cohort_k=args.cohort))
    params = model.init(jax.random.PRNGKey(0))
    sampler = make_client_stream(jax.random.PRNGKey(1), args.cohort, args.vocab)

    # host-driven round loop: the tracker is fed directly (repro.telemetry,
    # DESIGN.md §15) — StdoutTracker prints on the historical cadence and
    # --telemetry adds a machine-readable JSONL stream of EVERY round
    tracker = StdoutTracker(every=5, prefix="lm ")
    if args.telemetry is not None:
        tracker = CompositeTracker(tracker, JsonlTracker(args.telemetry))
    tracker.start_phase("train", 0)
    for t in range(args.rounds):
        kd = jax.random.fold_in(jax.random.PRNGKey(2), t)
        toks = jnp.stack([
            sampler(jax.random.fold_in(kd, i), i, args.tau, args.batch, args.seq + 1)
            for i in range(args.cohort)])
        batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
        t0 = time.time()
        params, metrics = step(params, batch, jax.random.fold_in(jax.random.PRNGKey(3), t))
        tracker.log(t, {"loss": float(metrics["loss"]),
                        "eta": float(metrics["eta_g"]),
                        "update_norm": float(metrics["mean_update_norm"]),
                        "round_time_s": time.time() - t0})
    tracker.finish()
    path = ckpt.save_checkpoint(args.ckpt_dir, args.rounds, params,
                                extra={"algorithm": args.algorithm})
    print(f"checkpoint -> {path}")


if __name__ == "__main__":
    use_compile_cache()
    main()
