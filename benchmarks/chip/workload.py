"""What the drives share: seeds, harness spans, sparse patterns and the GCN
adjacency, and the look-up of a benchmark file by its name.

A traffic mix (``traffic/<mix>.json``) names its drive, ``drives/<drive>.py``,
and a configuration names its pattern generator, ``patterns/<generator>.py``.
The harness finds each by that name, so a new mix, drive or generator is a
new file and edits none that is here.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
SPAN = "bench."


def load(kind: str, name: str):
    """The benchmark file ``<kind>/<name>.py`` as a module (file names may
    hold dots and dashes)."""
    return load_path(HERE / kind / f"{name}.py", f"{kind}_{name}")


def load_path(path: Path, name: str):
    """Import a file by its path."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit seed that mixes every bit of ``seed`` (JAX's PRNGKey keeps
    only the low 32 bits of a larger one)."""
    return int(np.random.SeedSequence([seed, salt]).generate_state(1)[0])


def annotate(name: str):
    """A profiler span ``bench.<name>`` around a call into the program."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN + name)


def csr_from_edges(rows, cols, n: int):
    """(indptr, indices) of the unique edges, sorted by row then column."""
    key = np.unique(rows.astype(np.int64) * n + cols.astype(np.int64))
    rows, cols = key // n, key % n
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr[1:], rows, 1)
    return np.cumsum(indptr).astype(np.int32), cols.astype(np.int32)


def pattern(spec: dict, n: int, avg_degree: float):
    """(indptr, indices) of a configuration's pattern: ``spec["generator"]``
    names ``patterns/<generator>.py``, which reads the rest of ``spec``."""
    return load("patterns", spec["generator"]).generate(spec, n, avg_degree)


def gcn_adjacency(indptr, indices, n: int):
    """A_hat = D^-1/2 (A + I) D^-1/2, D the row counts of A + I, as
    (indptr, rows, cols, values float32)."""
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    diag = np.arange(n, dtype=np.int64)
    indptr, cols = csr_from_edges(np.concatenate([rows, diag]), np.concatenate([indices, diag]), n)
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    dinv = 1.0 / np.sqrt(np.diff(indptr).astype(np.float64))
    vals = (dinv[rows] * dinv[cols]).astype(np.float32)
    return indptr, rows, cols, vals
