#!/usr/bin/env python3
"""Chip smoke: drive the SpGEMM main path once on a TPU, in one process.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded Economics
                                      # self-product vs the one-chip run

Phases (data generated from ``--seed`` by ``repro.apps.graphs``; nothing is
downloaded), each through the public API with default knobs unless named:

* self-product ``spgemm(a, a)`` at published Table II row counts —
  Economics (206k rows, flat degree) and p2p-Gnutella04 (10,876 rows,
  R-MAT power law) — with defaults (``sort`` + AIA gather),
  ``engine="fused_hash"`` (planned sizing, zero host syncs) and
  ``engine="auto"``; every C matches a SciPy host reference, and
  ``fused_hash`` is bit-identical to ``engine="hash"``;
* ``mcl``: three iterations on an R-MAT graph of p2p-Gnutella04's degree
  at ``MCL_ROWS`` rows vs the same call with ``gather="xla"``;
* ``train_gnn``: five full-batch GCN steps at ogbn-arxiv's shape (169,343
  nodes, 128 features, 40 classes) with ``sparse_mode="topk"``; step 1
  matches a ``gather="xla"`` run;
* ``SpGEMMService``: 16 requests from 4 tenants over shared patterns; at
  least one batched dispatch, nothing replayed or quarantined, every
  result equal to a per-request ``spgemm``.

The run fails (non-zero exit, no result line) without a TPU, with
``REPRO_KERNEL_BACKEND`` set, when the default knobs would not pick the
Pallas kernels, or when a program that ran lacks its compiled kernel
(``tpu_custom_call``).  Per-phase lines carry smoke timings (host wall
seconds of one cold call, compilation included), compile seconds,
``peak_bytes_in_use`` and nnz; the last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# Published sizes: Table II rows (Economics, p2p-Gnutella04) and the
# ogbn-arxiv node/feature/class counts of Table III.
ECONOMICS_ROWS = 206_000
GNUTELLA_ROWS = 10_876
ARXIV_NODES = 169_343
ARXIV_FEATURES = 128
ARXIV_CLASSES = 40
# The OGB reference GCN for ogbn-arxiv: 3 layers, 256 hidden channels.
ARXIV_LAYERS = 3
ARXIV_HIDDEN = 256
MCL_ITERS = 3
# MCL's second expansion at the published 10,876 rows pads to ~26 GB of
# intermediate-product stream (ELL width a_cap x kb_cap of the hub rows of
# A^2), more than the chip's 16 GB; at 2,048 rows the third expansion pads
# to ~1 GB.
MCL_ROWS = 2048
GNN_STEPS = 5
SERVE_REQUESTS = 16
SERVE_TENANTS = 4
SERVE_PATTERNS = 3
SERVE_N = 2048
SERVE_DENSITY = 0.004

GATHER_KERNEL = 'kernel_name = "aia_gather_rows"'
HASH_KERNEL = 'kernel_name = "hash_accumulate"'


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, missing or uncompiled result."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Measurement helpers
# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]
_COMPILES = [0]


def _on_event_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration
        _COMPILES[0] += 1


@contextlib.contextmanager
def _phase(label: str, **fields):
    """Time one cold call (compile included) and print its phase line."""
    import jax

    c0, n0 = _COMPILE_S[0], _COMPILES[0]
    t0 = time.perf_counter()
    out = {}
    yield out
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    parts = [f"phase={label}"] + [f"{k}={v}" for k, v in fields.items()]
    parts += [f"{k}={v}" for k, v in out.items()]
    parts += [f"smoke_wall_s={wall!r}", f"compile_s={_COMPILE_S[0] - c0!r}",
              f"compiles={_COMPILES[0] - n0}",
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}"]
    print("[smoke] " + " ".join(parts), flush=True)


def _all_contain(texts, marker: str) -> bool:
    return bool(texts) and all(marker in t for t in texts)


# ---------------------------------------------------------------------------
# Result checks
# ---------------------------------------------------------------------------

def _host_csr(c):
    nnz = int(np.asarray(c.indptr)[-1])
    return (np.asarray(c.indptr), np.asarray(c.indices)[:nnz],
            np.asarray(c.data)[:nnz])


def scipy_product(a):
    """Independent host reference ``A @ A`` (SciPy, float32, sorted)."""
    import scipy.sparse as sp

    ip, ix, dt = _host_csr(a)
    m = sp.csr_matrix((dt, ix, ip), shape=a.shape)
    ref = (m @ m).tocsr()
    ref.sort_indices()
    return ref


def matches_reference(c, ref, rtol: float = 1e-5) -> bool:
    """Identical pattern, values within float32 accumulation tolerance."""
    ip, ix, dt = _host_csr(c)
    return (np.array_equal(ip, ref.indptr) and np.array_equal(ix, ref.indices)
            and np.allclose(dt, ref.data, rtol=rtol, atol=1e-6))


def bit_identical(c1, c2) -> bool:
    return all(np.array_equal(x, y)
               for x, y in zip(_host_csr(c1), _host_csr(c2)))


def _ready(c):
    import jax

    jax.block_until_ready((c.indptr, c.indices, c.data))
    return c


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def table_ii(name: str, rows: int, seed: int):
    from repro.apps.graphs import table_ii_matrix

    return table_ii_matrix(name, seed=seed, n_override=rows)


def phase_selfproduct(name: str, a, expect_kernels: bool = True) -> dict:
    """Self-product under defaults, fused_hash, auto and hash vs SciPy."""
    from repro.core import cache_stats, executor, spgemm

    ref = scipy_product(a)
    results = {}
    with executor.record_lowerings() as texts:
        for variant, kw in (("default", {}), ("fused_hash",
                                                {"engine": "fused_hash"}),
                            ("auto", {"engine": "auto"}),
                            ("hash", {"engine": "hash"})):
            n_before = {k: len(v) for k, v in texts.items()}
            syncs = cache_stats()["host_sync_count"]
            with _phase("selfprod", matrix=name, variant=variant) as out:
                res = spgemm(a, a, **kw)
                _ready(res.c)
                if variant == "fused_hash":
                    out["host_sync_count"] = (
                        cache_stats()["host_sync_count"] - syncs)
                out["nnz_c"] = res.info["nnz_c"]
                ok = matches_reference(res.c, ref)
                out["matches_scipy"] = ok
            _require(ok, f"{name} {variant}: C differs from the SciPy "
                         "reference")
            if variant == "fused_hash":
                _require(out["host_sync_count"] == 0,
                         f"{name} fused_hash paid host syncs")
            results[variant] = (res, {k: texts[k][n_before[k]:]
                                      for k in texts})
    _require(bit_identical(results["fused_hash"][0].c, results["hash"][0].c),
             f"{name}: fused_hash is not bit-identical to hash")
    kernels = {
        "gather_compiled": _all_contain(
            results["default"][1]["enumerate"], GATHER_KERNEL),
        "hash_compiled": _all_contain(
            results["fused_hash"][1]["fused"], HASH_KERNEL)
        and _all_contain(results["fused_hash"][1]["fused"], GATHER_KERNEL),
    }
    print(f"[smoke] phase=selfprod matrix={name} check=kernels "
          + " ".join(f"{k}={v}" for k, v in kernels.items()), flush=True)
    if expect_kernels:
        _require(all(kernels.values()),
                 f"{name}: a program ran without its compiled kernel "
                 f"{kernels}")
    return {"nnz_c": results["default"][0].info["nnz_c"], **kernels}


def phase_mcl(g, iters: int = MCL_ITERS) -> dict:
    """MCL with defaults vs the same call with the XLA gather."""
    from repro.apps.markov_clustering import mcl

    runs = {}
    for label, kw in (("default", {}),
                      ("xla", {"gather": "xla", "method": "sort"})):
        with _phase("mcl", variant=label) as out:
            r = mcl(g, max_iters=iters, **kw)
            _ready(r.matrix)
            out["iterations"] = r.n_iterations
            out["nnz"] = int(np.asarray(r.matrix.indptr)[-1])
        runs[label] = r
    d, x = runs["default"], runs["xla"]
    _require(d.n_iterations == x.n_iterations, "mcl iteration counts differ")
    di, xi = _host_csr(d.matrix), _host_csr(x.matrix)
    _require(np.array_equal(di[0], xi[0]) and np.array_equal(di[1], xi[1])
             and np.allclose(di[2], xi[2], rtol=1e-5, atol=1e-7),
             "mcl with the AIA gather differs from gather='xla'")
    _require(np.array_equal(d.clusters, x.clusters), "mcl clusters differ")
    return {"iterations": d.n_iterations}


def phase_gnn(nodes: int = ARXIV_NODES, d_in: int = ARXIV_FEATURES,
              n_classes: int = ARXIV_CLASSES, hidden: int = ARXIV_HIDDEN,
              n_layers: int = ARXIV_LAYERS, steps: int = GNN_STEPS,
              seed: int = 0, expect_kernels: bool = True) -> dict:
    """Full-batch GCN training at ogbn-arxiv's shape, TopK aggregation."""
    import jax

    from repro.apps.gnn import (GNNConfig, gnn_forward, init_gnn,
                                normalize_adjacency, train_gnn)
    from repro.apps.graphs import TABLE_III_SCALED, rmat_graph

    deg = TABLE_III_SCALED["ogbn-arxiv"][1]
    a = normalize_adjacency(rmat_graph(nodes, deg, seed=seed))
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((nodes, d_in)).astype(np.float32)
    labels = rng.integers(0, n_classes, nodes)
    cfg = GNNConfig(arch="gcn", n_layers=n_layers, d_in=d_in,
                    d_hidden=hidden, n_classes=n_classes,
                    sparse_mode="topk", gather="auto")
    with _phase("gnn", variant="auto", nodes=nodes, steps=steps) as out:
        _, hist = train_gnn(cfg, a, x, labels, n_steps=steps, seed=seed)
        out["loss_first"] = hist[0]
        out["loss_last"] = hist[-1]
    with _phase("gnn", variant="xla", nodes=nodes, steps=1) as out:
        _, hist_x = train_gnn(dataclasses.replace(cfg, gather="xla"), a, x,
                              labels, n_steps=1, seed=seed)
        out["loss_first"] = hist_x[0]
    _require(bool(np.all(np.isfinite(hist))), f"gnn loss not finite: {hist}")
    _require(np.isclose(hist[0], hist_x[0], rtol=1e-5, atol=1e-6),
             f"gnn step-1 loss {hist[0]} differs from gather='xla' "
             f"{hist_x[0]}")
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    text = jax.jit(lambda p: gnn_forward(cfg, p, a, jax.numpy.asarray(x))
                   ).lower(params).as_text()
    compiled = GATHER_KERNEL in text
    print(f"[smoke] phase=gnn check=kernels gather_compiled={compiled}",
          flush=True)
    if expect_kernels:
        _require(compiled, "gnn aggregation ran without the AIA kernel")
    return {"losses": hist, "loss_xla": hist_x[0]}


def phase_service(n: int = SERVE_N, density: float = SERVE_DENSITY,
                  requests: int = SERVE_REQUESTS,
                  tenants: int = SERVE_TENANTS,
                  patterns: int = SERVE_PATTERNS, seed: int = 0) -> dict:
    """SpGEMMService over serve.py's multi-tenant trace vs per-request."""
    from repro.core import spgemm
    from repro.launch.serve import spgemm_trace
    from repro.serve import SpGEMMService

    trace = spgemm_trace(n=n, density=density, patterns=patterns,
                         requests=requests, tenants=tenants, seed=seed)
    svc = SpGEMMService(max_batch=requests, max_wait=3600.0)
    with _phase("service", requests=requests, tenants=tenants) as out:
        tickets = [svc.submit(t, a, b) for t, a, b in trace]
        svc.flush()
        got = [_ready(t.result().c) for t in tickets]
        st = svc.stats()
        for k in ("dispatches", "batched_dispatches", "replayed",
                  "quarantined", "requests_completed"):
            out[k] = st[k]
    _require(st["requests_completed"] == requests,
             f"service completed {st['requests_completed']}/{requests}")
    _require(st["batched_dispatches"] >= 1, "service never batched")
    _require(st["replayed"] == 0 and st["quarantined"] == 0,
             f"service replayed {st['replayed']} and quarantined "
             f"{st['quarantined']} requests")
    for (_, a, b), c in zip(trace, got):
        _require(bit_identical(c, _ready(spgemm(a, b).c)),
                 "a service result differs from per-request spgemm")
    return {k: st[k] for k in ("dispatches", "batched_dispatches",
                               "replayed", "quarantined")}


def phase_sharded(a, n_chips: int) -> dict:
    """Sharded self-product on an ``n_chips`` mesh vs one chip."""
    from repro.core import spgemm
    from repro.launch.mesh import make_spgemm_mesh

    mesh = make_spgemm_mesh(n_chips)
    with _phase("sharded", chips=1) as out:
        one = _ready(spgemm(a, a).c)
        out["nnz_c"] = int(np.asarray(one.indptr)[-1])
    with _phase("sharded", chips=n_chips) as out:
        many = _ready(spgemm(a, a, mesh=mesh, operands="auto").c)
        out["nnz_c"] = int(np.asarray(many.indptr)[-1])
        out["bit_identical"] = bit_identical(one, many)
    _require(out["bit_identical"],
             f"{n_chips}-chip result differs from the one-chip result")
    _require(matches_reference(one, scipy_product(a)),
             "one-chip result differs from the SciPy reference")
    return {"nnz_c": out["nnz_c"]}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def check_platform(chips: int) -> dict:
    """The device JAX reports; fails off-TPU or with a backend override."""
    import jax

    from repro.core.executor import resolve_gather
    from repro.kernels.ops import resolve_backend

    if os.environ.get("REPRO_KERNEL_BACKEND"):
        raise SmokeFailure("REPRO_KERNEL_BACKEND is set; the smoke runs the "
                           "default kernel resolution only")
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX reports {dev.platform!r} devices")
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} but JAX sees {len(devs)}")
    _require(resolve_gather("auto") == "aia",
             "gather='auto' does not resolve to the AIA kernels")
    _require(resolve_backend("auto") == "pallas",
             "the kernel backend does not resolve to compiled Pallas")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def run(chips: int, seed: int) -> None:
    """Every phase for ``chips == 1``; only the sharded phase otherwise."""
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    economics = table_ii("Economics", ECONOMICS_ROWS, seed)
    if chips > 1:
        phase_sharded(economics, chips)
        return
    phase_selfproduct("Economics", economics)
    del economics
    gnutella = table_ii("p2p-Gnutella04", GNUTELLA_ROWS, seed)
    phase_selfproduct("p2p-Gnutella04", gnutella)
    del gnutella
    phase_mcl(table_ii("p2p-Gnutella04", MCL_ROWS, seed))
    phase_gnn(seed=seed)
    phase_service(seed=seed)


def main(argv=None) -> int:
    """Run the smoke; 0 and the JSON result line only when every phase
    passed."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded Economics self-product on a "
                         "four-chip mesh and the one-chip run it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable ({e}); run "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    try:
        device = check_platform(args.chips)
        enable_compile_cache()
        print(f"[smoke] device platform={device['platform']} "
              f"kind={device['kind']!r} count={device['count']} "
              f"jax={jax.__version__}", flush=True)
        run(args.chips, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if device["count"] != args.chips:
        print(f"chip_smoke: FAILED: ran with --chips {args.chips} on "
              f"{device['count']} devices", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
