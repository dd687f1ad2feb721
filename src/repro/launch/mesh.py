"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax import;
tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "make_client_mesh",
           "auto_shard_count", "auto_chunk_clients", "client_shard_spec"]

# Minimum clients per shard for the "auto" shard-count heuristic.  Measured
# on the e7 quick geometry (M=96, 8 forced host devices): 8 shards put only
# 12 clients on each device and throughput COLLAPSED to ~0.37x of the
# 4-shard mesh (BENCH_engine.json history) — per-round shard_map/psum
# overhead dominates once the per-device slice is that thin.  24 clients per
# shard is the knee of that curve (4 shards at M=96).
MIN_CLIENTS_PER_SHARD = 24


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes: the engines place arrays with
    ``shard_map`` specs and ``with_sharding_constraint``, which refuse the
    ``Explicit`` axes ``make_mesh`` builds by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for unit tests."""
    return _auto_mesh((data, model), ("data", "model"))


def make_client_mesh(n_shards: int | None = None, *, axis: str = "clients"):
    """1-D ``clients`` mesh for the client-sharded round engine (DESIGN.md §9).

    ``n_shards`` defaults to every visible device.  On CPU, force multiple
    host devices BEFORE the first jax import to exercise real sharding:

        XLA_FLAGS=--xla_force_host_platform_device_count=8
    """
    n = n_shards if n_shards is not None else len(jax.devices())
    return _auto_mesh((n,), (axis,))


def auto_shard_count(num_clients: int, *, n_devices: int | None = None,
                     min_clients_per_shard: int = MIN_CLIENTS_PER_SHARD) -> int:
    """Shard count capped so every shard holds >= ``min_clients_per_shard``.

    Using every visible device is NOT always fastest: past the point where a
    device's cohort slice is thin, per-round shard_map/psum overhead eats the
    parallelism (the 8-shard collapse recorded in BENCH_engine.json — see
    ``MIN_CLIENTS_PER_SHARD``).  This caps the mesh at
    ``num_clients // min_clients_per_shard`` shards, floored at 1.
    """
    n_dev = n_devices if n_devices is not None else len(jax.devices())
    return max(1, min(n_dev, num_clients // min_clients_per_shard))


def device_memory_budget(*, fraction: float = 0.25,
                         fallback_bytes: int = 4 << 30) -> int:
    """Bytes of device memory the streaming engine may spend on one chunk.

    Reads the live device's ``memory_stats()["bytes_limit"]`` when the
    backend exposes it (GPU/TPU) and budgets ``fraction`` of it — the rest
    stays free for the model, optimizer state, moments, and XLA temporaries.
    CPU backends report no limit; the documented fallback is 4 GiB, matching
    the host-RAM assumption of the docs/scaling.md sizing table.  A TPU that
    reports no limit is an error: guessing would size chunks for the wrong
    device.
    """
    device = jax.devices()[0]
    limit = int((device.memory_stats() or {}).get("bytes_limit", 0))
    if limit <= 0:
        if device.platform == "tpu":
            raise RuntimeError(
                f"{device.device_kind} reports no memory_stats()['bytes_limit']"
                "; pass budget_bytes= explicitly")
        limit = fallback_bytes
    return int(limit * fraction)


def auto_chunk_clients(dim: int, client_bytes: int = 0, *,
                       n_shards: int = 1,
                       budget_bytes: int | None = None) -> int:
    """Chunk size for ``StreamSpec(chunk_clients="auto")`` (DESIGN.md §12/§14).

    The docs/scaling.md sizing rule, inverted: a streamed chunk's peak device
    footprint is ~``chunk * (2 * 4 * dim + client_bytes)`` — the (c, d)
    update block, an equal-shape randomization block (the LDP noise
    materialization doubles the update memory; clip-only mechanisms simply
    leave headroom), and the chunk's staged client data — so the chunk is the
    memory budget divided by that per-client cost.  Mirrors
    ``auto_shard_count``: a heuristic with an explicit knob
    (``budget_bytes``), not a guarantee.  With ``n_shards`` > 1 each shard
    streams concurrently on its own device, so the budget is per-shard
    already and no division applies.

    Raises when even ``chunk_clients=1`` exceeds the budget — streaming
    cannot help then, and silently returning 1 would OOM one client at a
    time.
    """
    if dim < 1:
        raise ValueError(f"dim must be >= 1, got {dim}")
    per_client = 2 * 4 * int(dim) + max(0, int(client_bytes))
    budget = budget_bytes if budget_bytes is not None else device_memory_budget()
    chunk = budget // per_client
    if chunk < 1:
        raise ValueError(
            f"chunk_clients='auto': one client costs ~{per_client} bytes "
            f"(2 * 4 * dim={dim} update/noise rows + {client_bytes} data "
            f"bytes) but the device budget is {budget} bytes — even "
            "chunk_clients=1 cannot fit.  Shrink the model dimension, shard "
            "clients over more devices, or pass a larger budget_bytes.")
    return int(chunk)


def client_shard_spec(n_shards: int | str | None = None, *,
                      axis: str = "clients",
                      num_clients: int | None = None):
    """A ready ``ShardSpec`` for the session API over a fresh client mesh:

        FederatedSession(..., shard=client_shard_spec())

    is the one-liner for "shard the cohort over every visible device"
    (DESIGN.md §10), and

        client_shard_spec("auto", num_clients=M)

    applies the ``auto_shard_count`` heuristic — every device, but never so
    many that a shard's cohort slice drops below the measured efficiency
    floor.  Imported lazily so this module still never touches fedsim at
    import time.
    """
    if n_shards == "auto":
        if num_clients is None:
            raise ValueError("client_shard_spec('auto') requires num_clients=")
        n_shards = auto_shard_count(num_clients)
    from repro.fedsim.specs import ShardSpec
    return ShardSpec(mesh=make_client_mesh(n_shards, axis=axis), client_axis=axis)
