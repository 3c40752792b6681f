"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Fast-mode defaults keep the whole
suite under a few minutes on CPU; pass --full for the larger workloads used
in EXPERIMENTS.md.

Multi-device: ``--devices N`` forces N host devices (XLA_FLAGS is set
*before* jax is imported, so this must be the process entry point) and runs
every SpGEMM through the sharded executor on a ``("shard",)`` mesh.

CI: ``--ci`` runs a tiny synthetic-graph smoke suite and ``--json PATH``
writes the records for the bench-smoke regression gate
(``benchmarks/check_regression.py`` compares against the committed
``benchmarks/BENCH_baseline.json``).

Amortized paths: ``--batch N`` adds batched-vs-looped SpGEMM records
(one plan serving N same-pattern value sets vs N independent ``spgemm``
calls) and ``--reuse-plan`` adds a plan-cache-served self-product record;
both also fold the executor's ``cache_stats()`` into the JSON meta so CI
can assert nonzero plan-cache hits from the artifact alone.

Pipelining: the CI smoke always emits a ``ci_selfprod_pipelined`` vs
``ci_selfprod_legacy`` pair on a forced multi-chunk plan and writes a
``pipeline_probe`` into the JSON meta (blocking allocate syncs per call on
each path) so the workflow can gate ``host_sync_count`` ≤ waves, not
per-chunk; ``--pipeline`` switches the sync structure for the full suite.

Fused engine: the CI smoke loops its self-product records over every
engine in ``core.executor.available_engines()`` (so ``fused_hash`` and any
future registration are benched automatically), adds a ``ci_selfprod_fused``
multi-chunk probe, and writes a ``fused_probe`` into the JSON meta whose
``host_syncs_fused`` the workflow gates at **zero** — the plan-derived
sizing contract.  ``--sizing`` switches the sizing policy for the full
suite.

Serving: the CI smoke replays one Zipf-popular multi-tenant trace through
the pattern-coalescing ``SpGEMMService`` and a per-request service
(``benchmarks/bench_serve.py``), emitting a ``ci_serve_coalesced`` /
``ci_serve_per_request`` record pair plus a ``serve_probe`` meta dict
(coalescing ratio, p50/p99 latency, per-tenant quota audit) gated by
``assert_ci.py --serve-gate``.

Streaming: both smoke tiers run the out-of-core row-block lane
(``spgemm_streamed``) against the monolithic lane on the tier's
self-product graph, emitting a ``{tier}_selfprod_streamed`` /
``{tier}_selfprod_stream_mono`` record pair plus a ``stream_probe`` meta
dict (bit-exactness verdict, tile/H2D/overlap counter deltas) gated by
``assert_ci.py --stream-gate``.

Operand placement: under ``--devices >= 2`` both smoke tiers append an
``operand_probe`` to the JSON meta — a banded-graph self-product run under
``operands="replicate"`` then ``operands="footprint"``, recording the
B-side bytes/rows actually placed on shard devices in each mode so CI can
gate footprint bytes strictly below replicated bytes from the artifact
alone (``benchmarks/assert_ci.py --operand-gate``).

Resilience: the CI smoke runs a chaos probe (docs/resilience.md) — a
forced ``capacity_undersize`` fault through the fused planned lane, a
clean planned run, and an over-budget MCL expansion through the
``on_budget="stream"`` degradation — emitting ``ci_chaos_capacity_retry``
/ ``ci_chaos_degraded`` records plus a ``resilience_probe`` meta dict
(retry counter deltas, bit-exactness verdicts, clean-path sync/retry
counts) gated by ``assert_ci.py --resilience-gate``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

RECORDS: list = []
# Filled by the CI smoke's pipeline probe; written into the JSON meta so the
# workflow can gate host_sync_count ≤ waves (not per-chunk) from the artifact.
PIPELINE_PROBE: dict = {}
# Filled by the CI smoke's fused probe: blocking syncs of one fused-engine
# two-wave call (the plan-derived sizing contract is exactly zero).
FUSED_PROBE: dict = {}
# Filled by the medium tier's engine="auto" probe: converged-run autotune
# hit/miss deltas (the no-re-measurement contract) + the chosen per-bin
# assignment, so CI can gate the autotuner from the artifact alone.
AUTOTUNE_PROBE: dict = {}
# Filled by the communication-volume probe (multi-device tiers only):
# bytes/rows of B-side ELL buffers placed on shard devices under full
# replication vs footprint-gathered blocks, so CI can gate the
# communication-avoiding placement saving from the artifact alone.
OPERAND_PROBE: dict = {}
# Filled by the CI smoke's serving probe (benchmarks/bench_serve.py): the
# same Zipf trace replayed through a coalescing SpGEMMService and a
# per-request one, plus the per-tenant plan-quota audit, so CI can gate
# coalesced-beats-per-request and quota isolation from the artifact alone.
SERVE_PROBE: dict = {}
# Filled by the streaming probe (both smoke tiers): the streamed row-block
# lane's bit-exactness verdict vs the monolithic lane plus its tile /
# H2D-bytes / prefetch-overlap counter deltas, so CI can gate the
# out-of-core contract from the artifact alone (assert_ci --stream-gate).
STREAM_PROBE: dict = {}
# Filled by the CI smoke's chaos probe (docs/resilience.md): a forced
# capacity_undersize fault and an over-budget on_budget="stream" MCL,
# recording retry/degradation counter deltas, bit-exactness verdicts, and
# the clean planned path's sync/retry counts, so CI can gate every
# recovery path from the artifact alone (assert_ci --resilience-gate).
RESILIENCE_PROBE: dict = {}


def _emit(name, us, derived):
    RECORDS.append({"name": name, "us": float(f"{us:.0f}"), "derived": derived})
    print(f"{name},{us:.0f},{derived}")
    sys.stdout.flush()


def _make_mesh(n_devices: int):
    if n_devices <= 1:
        return None
    from repro.launch.mesh import make_spgemm_mesh

    return make_spgemm_mesh(n_devices)


def _operand_probe(mesh, row_chunk: int = 64) -> None:
    """Comm-volume probe (multi-device tiers only): one banded-graph
    self-product under ``operands="replicate"`` then ``"footprint"``,
    recording the B-placement byte/row deltas from ``cache_stats()``.

    A banded matrix keeps each shard's A-support inside a partial row band
    of B, so footprint blocks are genuinely smaller than replicas — the
    uniform smoke graphs have near-full footprints and would show no
    saving.  Deltas (not absolute counters) so the probe composes with the
    smoke records that already ran in this process; the banded pattern is
    fresh, so both runs are guaranteed operand-cache misses and the
    placement counters actually move."""
    if mesh is None or mesh.devices.size < 2:
        return
    import numpy as np
    from repro.core import executor
    from repro.core.spgemm import spgemm
    from repro.sparse.formats import csr_from_dense

    n, w = 256, 8
    rng = np.random.default_rng(7)
    dense = np.zeros((n, n), np.float32)
    for i in range(n):
        lo, hi = max(0, i - w), min(n, i + w + 1)
        dense[i, lo:hi] = rng.integers(1, 5, hi - lo)
    band = csr_from_dense(dense)

    keys = ("operand_bytes_placed", "operand_rows_footprint",
            "operand_rows_total")
    deltas = {}
    n_shards = 0
    for mode in ("replicate", "footprint"):
        s0 = executor.cache_stats()
        res = spgemm(band, band, mesh=mesh, row_chunk=row_chunk,
                     operands=mode)
        s1 = executor.cache_stats()
        deltas[mode] = {k: s1[k] - s0[k] for k in keys}
        n_shards = res.info["n_shards"]
    OPERAND_PROBE.update(
        n_shards=n_shards,
        bytes_replicated=deltas["replicate"]["operand_bytes_placed"],
        bytes_footprint=deltas["footprint"]["operand_bytes_placed"],
        rows_footprint=deltas["footprint"]["operand_rows_footprint"],
        rows_total=deltas["footprint"]["operand_rows_total"],
    )


def _stream_probe(mesh, a, prefix: str, tile_rows: int,
                  reps: int = 3) -> None:
    """Out-of-core probe: the streamed row-block lane vs the monolithic
    lane on the tier's self-product graph.

    Emits a ``{prefix}_selfprod_streamed`` / ``{prefix}_selfprod_stream_mono``
    record pair and fills ``STREAM_PROBE`` with the bit-exactness verdict
    plus the streamed lane's counter deltas over the timed reps — CI gates
    bit-exactness, real tiling (>= 2 tiles), prefetch/compute overlap, and
    the streamed-vs-monolithic overhead ratio from the artifact alone
    (``assert_ci.py --stream-gate``).  A per-run ``PlanCache`` is warmed
    first so the timed calls measure the steady-state streaming loop, not
    tile planning."""
    import jax
    import numpy as np
    from repro.core import executor
    from repro.core.spgemm import PlanCache, spgemm, spgemm_streamed

    cache = PlanCache()
    keys = ("tiles_streamed", "tile_bytes_h2d", "prefetch_overlap_hits")
    # warm both lanes: tile plans + compiled programs
    res_s = spgemm_streamed(a, a, tile_rows=tile_rows, mesh=mesh, plan=cache)
    res_m = spgemm(a, a, mesh=mesh)

    ipt_m = np.asarray(res_m.c.indptr)
    nnz = int(ipt_m[-1])
    bit_exact = (
        np.array_equal(np.asarray(res_s.c.indptr), ipt_m)
        and np.array_equal(np.asarray(res_s.c.indices)[:nnz],
                           np.asarray(res_m.c.indices)[:nnz])
        and np.array_equal(np.asarray(res_s.c.data)[:nnz],
                           np.asarray(res_m.c.data)[:nnz]))

    s0 = {k: executor.cache_stats()[k] for k in keys}
    best_s = best_m = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        rs = spgemm_streamed(a, a, tile_rows=tile_rows, mesh=mesh,
                             plan=cache)
        jax.block_until_ready(rs.c)
        best_s = min(best_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        rm = spgemm(a, a, mesh=mesh)
        jax.block_until_ready(rm.c)
        best_m = min(best_m, time.perf_counter() - t0)
    s1 = {k: executor.cache_stats()[k] for k in keys}
    # per-call deltas so the probe composes with earlier streamed runs
    deltas = {k: (s1[k] - s0[k]) // reps for k in keys}

    streamed_name = f"{prefix}_selfprod_streamed"
    mono_name = f"{prefix}_selfprod_stream_mono"
    STREAM_PROBE.update(
        bit_exact=bool(bit_exact),
        streamed_record=streamed_name, monolithic_record=mono_name,
        n_tiles=int(res_s.info["n_tiles"]),
        tile_rows=int(res_s.info["tile_rows"]),
        prefetch=int(res_s.info["prefetch"]),
        max_tile_ip=int(res_s.info["max_tile_ip"]),
        plan_hits=cache.hits,
        **deltas,
    )
    _emit(streamed_name, best_s * 1e6,
          f"tiles={res_s.info['n_tiles']};tile_rows={tile_rows};"
          f"bit_exact={int(bit_exact)};overlap={deltas['prefetch_overlap_hits']}")
    _emit(mono_name, best_m * 1e6,
          f"nnz_c={res_m.info['nnz_c']};shards={res_m.info['n_shards']}")


def _resilience_probe(mesh, a) -> None:
    """Chaos probe: force every executor recovery path and record that it
    recovered (docs/resilience.md).

    Three measurements on the CI smoke graph: (1) a ``capacity_undersize``
    fault through the fused planned lane — the device-side overflow flag
    must trip, the call must re-execute at measured capacity, and the
    result must be bit-exact vs ``sizing="measured"``; (2) a clean planned
    run — zero ``capacity_retries`` and zero blocking host syncs, the
    fast-path contract the retry machinery must not erode; (3) a
    self-product whose monolithic estimate exceeds a deliberately halved
    device budget, run through ``on_budget="stream"`` — the degradation
    must re-route to the streamed lane and match the un-budgeted product
    bit-exactly (integer-valued graph, so engines agree to the bit).
    Emits ``ci_chaos_capacity_retry`` / ``ci_chaos_degraded`` records and
    fills ``RESILIENCE_PROBE`` for ``assert_ci.py --resilience-gate``.
    """
    import jax
    import numpy as np
    from repro.core import executor, faults
    from repro.core.spgemm import spgemm
    from repro.sparse.formats import csr_to_dense

    # --- forced capacity undersize through the fused planned lane ---
    ref = spgemm(a, a, engine="fused_hash", mesh=mesh, sizing="measured")
    dref = csr_to_dense(ref.c)
    r0 = executor.cache_stats()["capacity_retries"]
    t0 = time.perf_counter()
    with faults.fault_injection("capacity_undersize"):
        res = spgemm(a, a, engine="fused_hash", mesh=mesh)
        jax.block_until_ready(res.c)
    retry_s = time.perf_counter() - t0
    retries_forced = executor.cache_stats()["capacity_retries"] - r0
    retry_bit_exact = bool(np.array_equal(csr_to_dense(res.c), dref))

    # --- clean planned run: the fast path must stay sync- and retry-free
    spgemm(a, a, engine="fused_hash", mesh=mesh)  # warm
    r0 = executor.cache_stats()["capacity_retries"]
    s0 = executor.cache_stats()["host_sync_count"]
    clean = spgemm(a, a, engine="fused_hash", mesh=mesh)
    jax.block_until_ready(clean.c)
    retries_clean = executor.cache_stats()["capacity_retries"] - r0
    syncs_clean = executor.cache_stats()["host_sync_count"] - s0

    _emit("ci_chaos_capacity_retry", retry_s * 1e6,
          f"retries={retries_forced};bit_exact={int(retry_bit_exact)};"
          f"clean_retries={retries_clean};clean_syncs={syncs_clean}")

    # --- over-budget call through the on_budget="stream" degradation ---
    # half the monolithic estimate: the call must degrade to the streamed
    # lane, while the graph's worst single row still fits a tile easily
    need = executor.estimated_device_bytes(
        ref.plan, np.dtype(np.float32).itemsize)
    budget = need // 2
    d0 = executor.cache_stats()["budget_degradations"]
    try:
        executor.set_device_budget(budget)
        t0 = time.perf_counter()
        deg = spgemm(a, a, mesh=mesh, on_budget="stream")
        jax.block_until_ready(deg.c)
        degraded_s = time.perf_counter() - t0
    finally:
        executor.set_device_budget(None)
    degradations = executor.cache_stats()["budget_degradations"] - d0
    degraded_bit_exact = bool(
        deg.info.get("degraded_to_stream") == 1
        and np.array_equal(csr_to_dense(deg.c), dref))

    _emit("ci_chaos_degraded", degraded_s * 1e6,
          f"degradations={degradations};"
          f"bit_exact={int(degraded_bit_exact)};"
          f"budget_bytes={budget}")

    RESILIENCE_PROBE.update(
        capacity_retries_forced=int(retries_forced),
        capacity_retry_bit_exact=bool(retry_bit_exact),
        capacity_retries_clean=int(retries_clean),
        host_syncs_clean=int(syncs_clean),
        budget_degradations=int(degradations),
        degraded_bit_exact=bool(degraded_bit_exact),
    )


def ci_smoke(mesh, batch: int = 0, reuse_plan: bool = False,
             pipeline: str = "two_wave", sizing: str = "auto") -> None:
    """Tiny synthetic-graph smoke run for the bench-smoke CI job.

    One spgemm self-product per *registered engine* (the loop reads
    ``core.executor.available_engines()``, so new engines are benched
    without editing this driver) and a 2-iteration MCL on a 256-node
    random graph; small enough for an ubuntu-latest runner, large enough
    that a pathological slowdown (re-tracing per iteration, broken cache
    keys) blows past the 2x regression gate.  ``batch``/``reuse_plan`` add
    the amortized-path records (batched vs per-matrix loop;
    plan-cache-served self-product) the workflow asserts on.  ``pipeline``
    switches the executor sync structure for every record except the
    explicit pipelined-vs-legacy and fused probes, which always run their
    own paths.
    """
    import jax
    import numpy as np
    from repro.apps.markov_clustering import mcl
    from repro.core.executor import available_engines
    from repro.core.spgemm import PlanCache, spgemm, spgemm_batched
    from repro.sparse.formats import csr_from_dense, csr_to_dense

    rng = np.random.default_rng(0)
    n = 256
    x = np.where(rng.random((n, n)) < 0.04,
                 rng.integers(1, 5, (n, n)), 0).astype(np.float32)
    a = csr_from_dense(x)

    for engine in available_engines():
        spgemm(a, a, engine=engine, mesh=mesh, pipeline=pipeline,
               sizing=sizing)  # warm the program cache
        # min over reps: the noise-robust statistic for a shared CI runner
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res = spgemm(a, a, engine=engine, mesh=mesh, pipeline=pipeline,
                         sizing=sizing)
            jax.block_until_ready(res.c)  # async dispatch: time ALL the work
            best = min(best, time.perf_counter() - t0)
        _emit(f"ci_selfprod_{engine}", best * 1e6,
              f"nnz_c={res.info['nnz_c']};shards={res.info['n_shards']}")

    # Two-wave vs legacy pipeline on a deliberately multi-chunk plan
    # (row_chunk=64 on a 256-row graph): the probe counts the blocking
    # allocate syncs of one call on each path — the pipelined one must pay
    # per *wave* (≤ 1), the legacy one per chunk.
    from repro.core.executor import cache_stats

    for pipe in ("two_wave", "legacy"):
        spgemm(a, a, engine="sort", mesh=mesh, row_chunk=64,
               pipeline=pipe)  # warm
        s0 = cache_stats()["host_sync_count"]
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res = spgemm(a, a, engine="sort", mesh=mesh, row_chunk=64,
                         pipeline=pipe)
            jax.block_until_ready(res.c)  # the benchmark's sync, not the
            # executor's: host_sync_count only counts pipeline-internal syncs
            best = min(best, time.perf_counter() - t0)
        syncs = (cache_stats()["host_sync_count"] - s0) // 3
        name = "ci_selfprod_pipelined" if pipe == "two_wave" \
            else "ci_selfprod_legacy"
        _emit(name, best * 1e6,
              f"host_syncs={syncs};nnz_c={res.info['nnz_c']};"
              f"shards={res.info['n_shards']}")
        key = "host_syncs_pipelined" if pipe == "two_wave" \
            else "host_syncs_legacy"
        PIPELINE_PROBE[key] = syncs

    # Fused zero-sync probe on the same forced multi-chunk plan: the fused
    # engine's plan-derived sizing must dispatch the whole call — all
    # chunks, device indptr, epilogue — without a single blocking host
    # sync.  The workflow gates host_syncs_fused == 0 from the artifact.
    spgemm(a, a, engine="fused_hash", mesh=mesh, row_chunk=64)  # warm
    s0 = cache_stats()["host_sync_count"]
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        res = spgemm(a, a, engine="fused_hash", mesh=mesh, row_chunk=64)
        jax.block_until_ready(res.c)
        best = min(best, time.perf_counter() - t0)
    # raw delta over ALL reps (no per-call averaging): the contract is
    # zero syncs, and a single stray sync must not floor-divide away
    syncs = cache_stats()["host_sync_count"] - s0
    _emit("ci_selfprod_fused", best * 1e6,
          f"host_syncs={syncs};nnz_c={res.info['nnz_c']};"
          f"shards={res.info['n_shards']}")
    FUSED_PROBE["host_syncs_fused"] = syncs

    if reuse_plan:
        # Plan-cache-served self-product: first call plans + populates,
        # timed calls skip Alg. 1 + Table-I binning entirely.
        cache = PlanCache()
        spgemm(a, a, engine="sort", mesh=mesh, plan=cache, pipeline=pipeline,
               sizing=sizing)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(spgemm(a, a, engine="sort", mesh=mesh,
                                         plan=cache, pipeline=pipeline,
                                         sizing=sizing).c)
            best = min(best, time.perf_counter() - t0)
        _emit("ci_selfprod_sort_reuse", best * 1e6,
              f"plan_hits={cache.hits};plan_misses={cache.misses}")

    if batch > 1:
        # Same-pattern value variants: one planned batched run vs a
        # per-matrix Python loop (the amortization headline).
        pattern = rng.random((n, n)) < 0.04
        mats = [csr_from_dense(np.where(
            pattern, rng.integers(1, 5, (n, n)), 0.0).astype(np.float32))
            for _ in range(batch)]
        b = mats[0]
        spgemm_batched(mats, b, engine="sort", mesh=mesh,
                       pipeline=pipeline, sizing=sizing)        # warm
        for m in mats:
            spgemm(m, b, engine="sort", mesh=mesh, pipeline=pipeline,
                   sizing=sizing)  # warm
        best_b = best_l = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res_b = spgemm_batched(mats, b, engine="sort", mesh=mesh,
                                   pipeline=pipeline, sizing=sizing)
            jax.block_until_ready(res_b.cs)
            best_b = min(best_b, time.perf_counter() - t0)
            t0 = time.perf_counter()
            res_l = [spgemm(m, b, engine="sort", mesh=mesh,
                            pipeline=pipeline, sizing=sizing) for m in mats]
            jax.block_until_ready([r.c for r in res_l])
            best_l = min(best_l, time.perf_counter() - t0)
        for mi, (cb, rl) in enumerate(zip(res_b.cs, res_l)):
            assert np.array_equal(np.asarray(csr_to_dense(cb)),
                                  np.asarray(csr_to_dense(rl.c))), (
                f"batched member {mi} diverged from its per-matrix "
                f"spgemm result (engine=sort, pipeline={pipeline})")
        _emit("ci_batched_sort", best_b * 1e6,
              f"batch={batch};nnz_c={res_b.info['nnz_c']};"
              f"shards={res_b.info['n_shards']}")
        _emit("ci_batched_loop_sort", best_l * 1e6,
              f"batch={batch};nnz_c={res_l[0].info['nnz_c']}")

    t0 = time.perf_counter()
    r = mcl(a, e=2, max_iters=2, tol=0.0, mesh=mesh, pipeline=pipeline,
            sizing=sizing)
    us = (time.perf_counter() - t0) * 1e6
    _emit("ci_mcl", us, f"iters={r.n_iterations};"
          f"clusters={len(np.unique(r.clusters))};"
          f"plan_hits={r.plan_cache_hits}")

    # Serving probe: one Zipf trace through the pattern-coalescing
    # SpGEMMService (max_batch=8) and a per-request service (max_batch=1);
    # both pay the full service path, so the record pair isolates what
    # coalescing into spgemm_batched buys.  assert_ci --serve-gate reads
    # the serve_probe meta.
    from benchmarks import bench_serve

    sv = bench_serve.run(mesh=mesh, requests=24, tenants=3, patterns=3,
                         n=128, max_batch=8)
    SERVE_PROBE.update(sv["serve_probe"])
    _emit("ci_serve_coalesced", sv["coalesced_s"] * 1e6,
          f"ratio={sv['serve_probe']['coalescing_ratio']:.2f};"
          f"batched={sv['serve_probe']['batched_dispatches']};"
          f"p99_ms={sv['serve_probe']['latency_p99_ms']:.1f}")
    _emit("ci_serve_per_request", sv["per_request_s"] * 1e6,
          f"dispatches={sv['serve_probe']['per_request_dispatches']};"
          f"speedup_x={sv['serve_probe']['speedup_x']:.2f}")

    _stream_probe(mesh, a, "ci", tile_rows=64)
    _operand_probe(mesh)
    _resilience_probe(mesh, a)


def medium_smoke(mesh, pipeline: str = "two_wave",
                 sizing: str = "auto") -> None:
    """Medium-scale smoke tier (``--tier medium``) — ``medium_*`` records.

    The CI tier's 256-node graph is so small that per-chunk sync overhead
    *beats* the two-wave pipeline (fixed dispatch cost dominates) and
    engine wall times sit inside timer noise.  This tier runs a graph big
    enough that sync elision wins and per-engine differences are stable:

    * ``medium_selfprod_{engine}`` — every registered engine on the same
      forced multi-chunk self-product (the single-engine bar the
      autotuner must match).
    * ``medium_selfprod_pipelined`` / ``medium_selfprod_legacy`` — the
      two-wave-vs-legacy pair on the *fused* engine, where the win the
      tiny tier can't show actually appears: the fused single-pass
      programs + planned zero-sync sizing beat the legacy per-chunk
      allocate-sync path by ~1.4x at this scale.  (On CPU runners host
      syncs are nearly free — host == device, no async dispatch queue —
      so the sort-engine two-wave pair stays within noise of legacy at
      any CI-affordable size; the fused lane is where sync structure
      changes the program count, not just the sync count.)
    * ``medium_selfprod_auto`` — ``engine="auto"`` through a dedicated
      ``AutotuneCache``: warm-up calls converge the per-bin measurement,
      then the timed runs must be pure hits.  The hit/miss deltas of the
      timed (converged) phase and the chosen assignment go into the JSON
      meta as ``autotune_probe`` — CI asserts hits > 0, misses == 0 (no
      re-measurement), and auto ≤ the best single engine within noise
      tolerance, all from the artifact.
    """
    import jax
    import numpy as np
    from repro.core import executor
    from repro.core.spgemm import spgemm
    from repro.sparse.formats import csr_from_dense

    rng = np.random.default_rng(1)
    n = 1024
    x = np.where(rng.random((n, n)) < 0.02,
                 rng.integers(1, 5, (n, n)), 0).astype(np.float32)
    a = csr_from_dense(x)
    row_chunk = 128  # 8 chunks: pipelining has real sync traffic to elide

    def timed(fn, reps=3):
        best = float("inf")
        res = None
        for _ in range(reps):
            t0 = time.perf_counter()
            res = fn()
            jax.block_until_ready(res.c)
            best = min(best, time.perf_counter() - t0)
        return best, res

    for engine in executor.available_engines():
        def run(engine=engine):
            return spgemm(a, a, engine=engine, mesh=mesh,
                          row_chunk=row_chunk, pipeline=pipeline,
                          sizing=sizing)
        run()  # warm the program cache
        best, res = timed(run)
        _emit(f"medium_selfprod_{engine}", best * 1e6,
              f"nnz_c={res.info['nnz_c']};shards={res.info['n_shards']}")

    for pipe in ("two_wave", "legacy"):
        def run(pipe=pipe):
            return spgemm(a, a, engine="fused_hash", mesh=mesh,
                          row_chunk=row_chunk, pipeline=pipe)
        run()  # warm
        best, res = timed(run)
        name = ("medium_selfprod_pipelined" if pipe == "two_wave"
                else "medium_selfprod_legacy")
        _emit(name, best * 1e6,
              f"engine=fused_hash;nnz_c={res.info['nnz_c']};"
              f"shards={res.info['n_shards']}")

    # engine="auto" through a dedicated cache: converge, then time.
    tuner = executor.AutotuneCache()

    def run_auto():
        return spgemm(a, a, engine="auto", mesh=mesh, row_chunk=row_chunk,
                      pipeline=pipeline, sizing=sizing, autotune=tuner)

    # one warm-up round per candidate engine converges every bin
    for _ in range(len(executor.available_engines()) + 1):
        run_auto()
    hits0, misses0 = tuner.hits, tuner.misses
    best, res = timed(run_auto)
    AUTOTUNE_PROBE.update(
        autotune_hits_converged=tuner.hits - hits0,
        autotune_misses_converged=tuner.misses - misses0,
        assignments=tuner.summary(),
    )
    _emit("medium_selfprod_auto", best * 1e6,
          f"nnz_c={res.info['nnz_c']};shards={res.info['n_shards']};"
          f"hits={tuner.hits - hits0};misses={tuner.misses - misses0}")

    _stream_probe(mesh, a, "medium", tile_rows=256)
    _operand_probe(mesh)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--engine", default="sort",
                    help="accumulation engine for the SpGEMM benchmarks; "
                         "validated against core.executor.available_engines()"
                         " after startup, so registered engines (including "
                         "fused_hash and future ones) are benchable without "
                         "editing this driver")
    ap.add_argument("--gather", default="xla", choices=("auto", "xla", "aia"),
                    help="B-row gather backend (Fig. 7 ablation axis)")
    ap.add_argument("--pipeline", default="two_wave",
                    choices=("two_wave", "legacy"),
                    help="executor sync structure: two_wave = one coalesced "
                         "allocate sync + device-side reassembly; legacy = "
                         "per-chunk syncs + NumPy reassembly (A/B baseline)")
    ap.add_argument("--sizing", default="auto",
                    choices=("auto", "planned", "measured"),
                    help="output sizing: planned = sync-free Alg. 1 bounds "
                         "(zero blocking host syncs; the fused_hash "
                         "default), measured = the uniqueCount-sync escape "
                         "hatch, auto = planned for fused engines")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the SpGEMM executor over the first N "
                         "devices of jax.devices(); on a CPU host this also "
                         "forces N host devices (sets XLA_FLAGS before "
                         "importing jax) — a TPU host's chips need no "
                         "forcing")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write records as JSON (bench-smoke artifact)")
    ap.add_argument("--ci", action="store_true",
                    help="tiny synthetic smoke suite for the CI gate "
                         "(alias of --tier ci)")
    ap.add_argument("--tier", default=None, choices=("ci", "medium"),
                    help="smoke tier: 'ci' = the tiny 256-node graph; "
                         "'medium' = a 1024-node graph big enough that "
                         "two-wave pipelining wins and per-engine "
                         "differences are stable, emitting medium_* "
                         "records plus the engine='auto' autotune probe")
    ap.add_argument("--batch", type=int, default=0, metavar="N",
                    help="add batched-SpGEMM records: one plan serving N "
                         "same-pattern value sets vs a per-matrix loop")
    ap.add_argument("--reuse-plan", action="store_true",
                    help="add plan-cache records (grouping skipped on "
                         "repeated sparsity patterns)")
    args = ap.parse_args()
    if args.batch == 1:
        ap.error("--batch needs N >= 2 (a batch of one has no loop to "
                 "amortize against); omit the flag to skip batched records")
    eng = args.engine

    if args.devices > 1:
        if "jax" in sys.modules:
            raise RuntimeError(
                "--devices must be set before jax is imported; run "
                "benchmarks/run.py as the process entry point")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = _make_mesh(args.devices)

    # --engine choices come from the live registry (not a frozen argparse
    # list) plus "auto"; imported only now because XLA_FLAGS must precede
    # jax import.  resolve_engine raises naming every valid choice.
    from repro.core.executor import available_engines, resolve_engine

    try:
        resolve_engine(args.engine)
    except ValueError as e:
        ap.error(str(e))

    tier = args.tier or ("ci" if args.ci else None)
    if tier is not None:
        if tier == "ci":
            ci_smoke(mesh, batch=args.batch, reuse_plan=args.reuse_plan,
                     pipeline=args.pipeline, sizing=args.sizing)
        else:
            medium_smoke(mesh, pipeline=args.pipeline, sizing=args.sizing)
        if args.json:
            _write_json(args.json, args)
        return

    from benchmarks import bench_self_product, bench_locality, \
        bench_graph_apps, bench_gnn

    # --- Table II / Fig 6: matrix self-product ---
    names = list(bench_self_product.run(
        names=None if args.full else ["scircuit", "p2p-Gnutella04",
                                      "Economics", "Protein"],
        n_override=None if args.full else 1024,
        methods=(eng,) if not args.full else available_engines(),
        gathers=(args.gather,), mesh=mesh))
    for r in names:
        _emit(f"selfprod_{r['workload']}", r[f"{eng}_ms"] * 1e3,
              f"gflops={r[f'{eng}_gflops']:.3f};ip={r['intermediate_products']};"
              f"nnz_c={r['nnz_c']};"
              f"vs_dense_pct={r[f'{eng}_vs_dense_reduction_pct']:.1f};"
              f"group_sched_pct={r['group_sched_reduction_pct']:.1f}")

    # --- Fig 5: locality / cache-hit proxy ---
    loc_names = ("scircuit", "cage15") if not args.full else \
        ("scircuit", "cage15", "web-Google")
    for r in bench_locality.run(names=loc_names,
                                n_override=None if args.full else 2048):
        _emit(f"locality_{r['workload']}", 0,
              f"hit_without_pct={r['without_aia_hit_pct']:.1f};"
              f"hit_with_pct={r['with_aia_hit_pct']:.1f};"
              f"round_trip_x={r['round_trip_reduction']:.1f}")

    # --- Fig 7/8: graph applications ---
    for r in bench_graph_apps.bench_contraction(
            names=("Economics", "Protein") if not args.full else
            ("RoadTX", "web-Google", "Economics", "amazon0601",
             "WindTunnel", "Protein"),
            n_override=None if args.full else 1024,
            engine=eng, gather=args.gather, mesh=mesh,
            pipeline=args.pipeline, sizing=args.sizing):
        _emit(f"contraction_{r['workload']}", r["spgemm_ms"] * 1e3,
              f"vs_dense_pct={r['reduction_vs_dense_pct']:.1f};ip={r['total_ip']}")
    for r in bench_graph_apps.bench_mcl(
            names=("Economics",) if not args.full else
            ("web-Google", "Economics", "Protein"),
            max_iters=2 if not args.full else 3,
            n_override=None if args.full else 1024,
            engine=eng, gather=args.gather, mesh=mesh,
            pipeline=args.pipeline, sizing=args.sizing):
        _emit(f"mcl_{r['workload']}", r["spgemm_ms"] * 1e3,
              f"vs_dense_pct={r['reduction_vs_dense_pct']:.1f};"
              f"clusters={r['n_clusters']};plan_hits={r['plan_hits']}")

    # --- Amortized batched path: one plan, N same-pattern value sets ---
    if args.batch > 1:
        for r in bench_graph_apps.bench_batched_selfprod(
                names=("Economics", "Protein") if not args.full else
                ("RoadTX", "web-Google", "Economics", "Protein"),
                batch=args.batch, n_override=None if args.full else 1024,
                engine=eng, gather=args.gather, mesh=mesh,
                pipeline=args.pipeline, sizing=args.sizing):
            _emit(f"batched_{r['workload']}", r["batched_ms"] * 1e3,
                  f"batch={r['batch']};loop_ms={r['loop_ms']:.1f};"
                  f"speedup_x={r['speedup_x']:.2f}")

    # --- Fig 10/11: GNN training ---
    for r in bench_gnn.run(
            datasets=("Flickr",) if not args.full else
            ("Flickr", "ogbn-arxiv", "Yelp"),
            archs=("gcn",) if not args.full else ("gcn", "gin", "sage"),
            n_steps=3 if not args.full else 8):
        _emit(f"gnn_{r['dataset']}_{r['arch']}", r["topk_s"] * 1e6,
              f"reduction_pct={r['reduction_pct']:.1f};"
              f"topk_loss={r['topk_final_loss']:.3f};"
              f"dense_loss={r['dense_final_loss']:.3f}")

    # --- Fig 9: scaling study ---
    s = bench_gnn.scaling_study(
        sizes=(512, 1024, 2048) if not args.full else (512, 1024, 2048, 4096))
    _emit("gnn_scaling", 0,
          "pearson_r={:.2f};reductions={}".format(
              s["pearson_r"],
              "/".join(f"{r['reduction_pct']:.0f}%" for r in s["rows"])))

    if args.json:
        _write_json(args.json, args)


def _write_json(path: str, args) -> None:
    from repro.core.executor import cache_stats

    meta = {"devices": args.devices, "engine": args.engine,
            "gather": args.gather, "ci": bool(args.ci),
            "tier": args.tier or ("ci" if args.ci else None),
            "full": bool(args.full), "batch": args.batch,
            "reuse_plan": bool(args.reuse_plan),
            "sizing": args.sizing,
            "cache_stats": cache_stats()}
    if PIPELINE_PROBE:
        meta["pipeline_probe"] = dict(PIPELINE_PROBE)
    if FUSED_PROBE:
        meta["fused_probe"] = dict(FUSED_PROBE)
    if AUTOTUNE_PROBE:
        meta["autotune_probe"] = dict(AUTOTUNE_PROBE)
    if OPERAND_PROBE:
        meta["operand_probe"] = dict(OPERAND_PROBE)
    if SERVE_PROBE:
        meta["serve_probe"] = dict(SERVE_PROBE)
    if STREAM_PROBE:
        meta["stream_probe"] = dict(STREAM_PROBE)
    if RESILIENCE_PROBE:
        meta["resilience_probe"] = dict(RESILIENCE_PROBE)
    with open(path, "w") as f:
        json.dump({"meta": meta, "records": RECORDS}, f, indent=2)
    print(f"wrote {len(RECORDS)} records to {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
