"""Runs of the harness with the timed path sound, broken, or replaced by the
lower-precision control, for the tests here and for readings on the chip.

On the chip, at a cell's own size (one process, one run per seed)::

    python3 benchmarks/chip/tests/harness_cases.py --cell economics.reuse \
        --case control --seconds 10 --seeds 11 12 13

prints one JSON line per seed with the numbers compared.  Cases:

* ``program``: the program as it is (the lower readings);
* ``control``: the reference computed one precision below the
  configuration's, in the program's place (each configuration's
  ``control``: a bfloat16 product, or matmuls of one bfloat16 pass);
* ``altered``: one value of each product altered where it is produced;
* ``unchanged``: every training step returns its state unchanged;
* ``half_batch``: the loss is the mean over half of the nodes.

``tiny_checkout`` copies the benchmark beside the program's sources with
the configurations cut to a size a CPU test holds: fewer rows and nodes,
the published widths.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import io
import json
import shutil
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TINY = {
    "economics": {"rows": 512},
    "ogbn-arxiv-gcn": {"nodes": 1024},
}


def tiny_checkout(dst: Path) -> Path:
    """A copy of the benchmark with tiny configurations, sharing ``src``."""
    shutil.copytree(
        CHIP, dst / "benchmarks" / "chip", ignore=shutil.ignore_patterns("tests", "__pycache__")
    )
    (dst / "src").symlink_to(REPO / "src")
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for name, sizes in TINY.items():
        path = dst / "benchmarks" / "chip" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(sizes)
        path.write_text(json.dumps(cfg))
    return dst


def load_run(root: Path):
    """The harness's entry module of the checkout at ``root``."""
    spec = importlib.util.spec_from_file_location("bench_run", root / "benchmarks/chip/run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reference(root: Path, config: str):
    path = root / "benchmarks" / "chip" / "configs" / f"{config}.py"
    spec = importlib.util.spec_from_file_location(f"case_reference_{config}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, json.loads(path.with_suffix(".json").read_text())


@contextlib.contextmanager
def _patched(module_name: str, attr: str, value):
    module = importlib.import_module(module_name)
    original = getattr(module, attr)
    setattr(module, attr, value)
    try:
        yield
    finally:
        setattr(module, attr, original)


def _host_csr(a):
    import numpy as np

    indptr = np.asarray(a.indptr)
    nnz = int(indptr[-1])
    return indptr, np.asarray(a.indices)[:nnz], np.asarray(a.data)[:nnz]


def _altered_spgemm(original):
    def spgemm(a, b, **kw):
        res = original(a, b, **kw)
        c = res.c
        res.c = type(c)(c.indptr, c.indices, c.data.at[0].multiply(1.001), c.shape)
        return res

    return spgemm


def _control_spgemm(reference):
    import jax.numpy as jnp
    import numpy as np

    from repro.sparse.formats import CSR

    class Result:
        def __init__(self, c):
            self.c = c

    def spgemm(a, b, **kw):
        indptr, indices, data = _host_csr(a)
        c = reference.control(indptr, indices, data, a.shape[0])
        return Result(
            CSR(
                jnp.asarray(c.indptr.astype(np.int32)),
                jnp.asarray(c.indices.astype(np.int32)),
                jnp.asarray(c.data.astype(np.float32)),
                a.shape,
            )
        )

    return spgemm


def _control_train(reference, cfg):
    import jax.numpy as jnp
    import numpy as np

    def train_gnn(gcfg, a, x, labels, n_steps=30, lr=1e-2, seed=0, mesh=None):
        indptr, cols, vals = _host_csr(a)
        rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int32), np.diff(indptr))
        _, params, losses, _ = reference.control(cfg, (rows, cols, vals), x, labels, seed, n_steps)
        return {k: jnp.asarray(v) for k, v in params.items()}, losses

    return train_gnn


def _half_batch_loss():
    import jax.numpy as jnp

    original = importlib.import_module("repro.apps.gnn")._loss_fn

    def loss_fn(cfg, params, a, x, labels, mask, mesh=None):
        half = jnp.where(jnp.arange(mask.shape[0]) < mask.shape[0] // 2, mask, 0.0)
        return original(cfg, params, a, x, labels, half, mesh=mesh)

    return loss_fn


@contextlib.contextmanager
def case(name: str, root: Path, config: str):
    """The program with fault or control ``name`` planted underneath."""
    if name == "program":
        yield
        return
    if name == "altered":
        mod = importlib.import_module("repro.core.spgemm")
        with _patched("repro.core.spgemm", "spgemm", _altered_spgemm(mod.spgemm)):
            yield
        return
    if name == "unchanged":
        with _patched("repro.apps.gnn", "apply_updates", lambda params, updates: params):
            yield
        return
    if name == "half_batch":
        with _patched("repro.apps.gnn", "_loss_fn", _half_batch_loss()):
            yield
        return
    if name == "control":
        reference, cfg = _reference(root, config)
        if "nodes" in cfg:
            with _patched("repro.apps.gnn", "train_gnn", _control_train(reference, cfg)):
                yield
        else:
            with _patched("repro.core.spgemm", "spgemm", _control_spgemm(reference)):
                yield
        return
    raise ValueError(f"unknown case {name!r}")


def run_cell(run, root: Path, cell: str, case_name: str, seed: int, seconds: float) -> dict:
    """One run of ``cell`` under ``case_name``; returns its result line."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    config = {w["name"]: w for w in manifest["workloads"]}[cell]["config"]
    out = io.StringIO()
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
    with case(case_name, root, config), contextlib.redirect_stdout(out):
        rc = run.main(argv)
    if rc != 0:
        raise RuntimeError(f"{cell} {case_name} seed {seed} exited {rc}")
    return json.loads(out.getvalue().strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--case", default="program")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    run = load_run(REPO)
    for seed in args.seeds:
        line = run_cell(run, REPO, args.cell, args.case, seed, args.seconds)
        record = {
            "cell": args.cell,
            "case": args.case,
            "seed": seed,
            "correct": line["correct"],
            "checks": {k: v["value"] for k, v in line["checks"].items()},
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        }
        print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
