"""Kernel backend resolution: one policy for every Pallas entry point.

``backend``:
  * "pallas"    — compiled Pallas (TPU target).
  * "interpret" — Pallas kernel body executed in Python (CPU validation).
  * "xla"       — pure-jnp fallback (also the software-only / "without AIA"
                  baseline).

``REPRO_KERNEL_BACKEND`` overrides the auto-detection.  Interpret mode runs
only when it is asked for (``interpret=True``, ``backend="interpret"`` or
the override) or where no compiled form exists (off-TPU); a TPU process
never falls back to interpreting a kernel on its own.
"""
from __future__ import annotations

import os
from typing import Literal

import jax

Backend = Literal["auto", "pallas", "interpret", "xla"]


def resolve_backend(backend: Backend = "auto") -> str:
    """``"auto"`` → the ``REPRO_KERNEL_BACKEND`` override if set, else
    ``"pallas"`` on TPU and ``"xla"`` elsewhere."""
    if backend != "auto":
        return backend
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        return os.environ["REPRO_KERNEL_BACKEND"]
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_interpret(interpret: bool | None) -> bool:
    """Compiled vs interpreted execution of a Pallas kernel called with
    ``interpret=None``.

    Resolve it *outside* any jitted body: the flag is a static argument, so
    resolving at trace time would bake the first call's override into the
    jit cache.  Off-TPU the interpreter is the only way to run a Pallas
    kernel; on a TPU an ``"xla"`` resolution means the caller routed a
    kernel it was told not to use, which is an error, not a reason to
    interpret.
    """
    if interpret is not None:
        return bool(interpret)
    be = resolve_backend("auto")
    if be == "pallas":
        return False
    if be == "interpret":
        return True
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"a Pallas kernel was called on TPU with the kernel backend "
            f"resolved to {be!r}; pass interpret=True to interpret it, or "
            "unset REPRO_KERNEL_BACKEND to run it compiled")
    return True
