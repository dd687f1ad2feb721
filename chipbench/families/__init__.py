"""One module per model family: everything of the harness that depends on
the model.  A configuration names its family (``"family": "cnn"``) and
``cell.family(cfg)`` imports ``chipbench.families.<family>``; the rest of the
harness (the session, the run, the reference's round, the comparison, the
trace readers) is the same for every family.

A family module provides these module-level functions; ``cfg`` is the
configuration as ``configs/<config>.json`` holds it:

- ``make_inputs(seed, cfg) -> {"batches", "test", "w0", "runs_key"}``: the
  per-client batches (a pytree whose leaves lead with the client axis, M),
  the held-out set, the initial weights (any pytree of float32 arrays) and
  the run-key stream, all from ``--seed`` through ``inputs.stream_keys``.
  The same seed gives the same inputs, and every seed the same shapes.
- ``program_loss(cfg)``: the ``loss(params, batch)`` the session trains and
  evaluates with, taken from the program's public entry points.
- ``ref_loss(params, batch, cfg)``: one client's loss on one batch, and
  ``ref_test_loss(params, test, cfg)``: the loss over the held-out set; both
  plain ``jax.numpy`` that import nothing of the program, traced inside the
  reference's jitted round (float32 at ``highest`` for the reference, lower
  for the control).
- ``ref_block(cfg)``: clients per block of the reference's ``lax.map``, a
  divisor of M, so that the reference fits on the chip beside nothing else.
- ``round_flops(cfg)``: model FLOPs of one round (every local step over
  every unmasked sample, forward and backward, plus the per-round eval), and
  ``params(cfg)``: the number of weights.

A new family is this one file, with its configuration, traffic, limits,
metric readers and ``BENCHMARK.json`` entries beside it: no other file of
the harness changes.
"""
