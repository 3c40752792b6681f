"""Mesh construction (multi-pod dry-run contract + the SpGEMM shard mesh).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod = (16, 16) chips over ("data", "model");
multi-pod = (2, 16, 16) over ("pod", "data", "model") — 2 × 256-chip v5e
pods.  The ``pod`` axis carries only data parallelism + the cross-pod
gradient all-reduce (optionally int8-compressed).

``make_spgemm_mesh`` builds the 1-D ``("shard",)`` mesh the sharded SpGEMM
executor partitions ``GroupPlan`` row ranges over.  Ambient mesh contexts
are entered with ``jax.set_mesh``.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device unit tests (host platform)."""
    return jax.make_mesh(shape, axes)


def make_spgemm_mesh(n_devices: int | None = None):
    """1-D ``("shard",)`` mesh for the sharded SpGEMM executor.

    Uses the first ``n_devices`` devices of ``jax.devices()`` (all of them
    by default): the chips of a TPU host as they are.  Only a CPU host
    needs its device count forced, with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax is
    imported (``benchmarks/run.py --devices N`` does this for you).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"requested {n} shard devices but only {len(devs)} are visible; "
            "on a CPU host, set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before "
            "importing jax")
    return jax.make_mesh((n,), ("shard",), devices=devs[:n])

