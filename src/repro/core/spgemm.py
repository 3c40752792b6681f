"""Public SpGEMM API — the paper's three-phase pipeline end-to-end.

``spgemm(A, B)`` reproduces the paper's flow:

  1. **Row-grouping** (host sync, like the paper's stream setup): Algorithm 1
     IP counts → Table-I groups → ``Map``.
  2. **Allocation + accumulation** per group, compiled and dispatched by the
     plan executor (``repro.core.executor``): cached jitted programs, one per
     (group shape, engine, gather backend) signature.
  3. **Reassembly** into one CSR in original row order via vectorized
     inverse-permutation scatters.

This module is a thin façade: engine registration, capacity policy, gather
backends, the program cache, and reassembly all live in the executor.

Amortized entry points (both delegate to the executor's amortization
layer):

* ``spgemm(..., plan=)`` — pass a ``GroupPlan`` to skip phase 1 outright,
  or a ``PlanCache`` to skip it whenever the operands' sparsity patterns
  were seen before (iterative workloads: MCL expansion at fixpoint,
  epoch-revisited GNN mini-batches).
* ``spgemm_batched`` — one planned pipeline run for a batch of
  same-pattern operands (values differ, structure shared); bit-identical
  to a per-matrix loop.

``spgemm_ell_fixed`` is the fully-jitted single-group variant (no host
syncs) for use inside ``scan``/training graphs (MCL iterations, GNN layers).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Literal, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor, phases
from repro.core.executor import PlanCache
from repro.core.grouping import GroupPlan, group_rows
from repro.sparse.formats import CSR, ELL

PlanLike = Union[GroupPlan, PlanCache, None]


@dataclasses.dataclass
class SpGEMMResult:
    """One SpGEMM product: the CSR result ``c``, the ``GroupPlan`` that
    executed it (reusable via ``spgemm(plan=...)``), and the ``info``
    counter dict (``nnz_c``, ``intermediate_products``, ``flops``,
    ``compression_ratio``, ``group_sizes``, ``n_shards``...)."""

    c: CSR
    plan: GroupPlan
    info: Dict[str, float]


@dataclasses.dataclass
class SpGEMMBatchResult:
    """Batched product: ``cs[i] = a_batch[i] @ b_batch[i]``; every member
    shares one output structure (indptr/indices are the same arrays)."""

    cs: List[CSR]
    plan: GroupPlan
    info: Dict[str, float]


def _resolve_plan(a: CSR, b: CSR, plan: PlanLike) -> GroupPlan:
    """Phase 1, amortized: reuse a given plan, consult a PlanCache, or run
    ``group_rows`` (the paper's per-matrix setup)."""
    with jax.profiler.TraceAnnotation("spgemm.plan"):
        if isinstance(plan, PlanCache):
            return plan.plan_for(a, b)
        if isinstance(plan, GroupPlan):
            return plan
        if plan is not None:
            raise TypeError(
                "plan must be a GroupPlan, PlanCache, or None; "
                f"got {type(plan)!r}")
        return group_rows(a, b)


def spgemm(
    a: CSR,
    b: CSR,
    method: Optional[Literal["hash", "sort"]] = None,
    row_chunk: int = 4096,
    schedule: Literal["grouped", "natural"] = "grouped",
    engine: Optional[str] = None,
    gather: executor.Gather = "auto",
    mesh=None,
    plan: PlanLike = None,
    pipeline: executor.Pipeline = "two_wave",
    sizing: executor.Sizing = "auto",
    autotune: Optional[executor.AutotuneCache] = None,
    operands: executor.Operands = "auto",
    operand_cache: Optional[executor.OperandCache] = None,
    on_budget: executor.OnBudget = "error",
) -> SpGEMMResult:
    """C = A @ B via the paper's multi-phase pipeline (plan-compiled).

    ``engine`` selects the allocation/accumulation engine from the executor
    registry (``"hash"``, ``"sort"``, ``"fused_hash"``; ``method`` is the
    legacy alias), or ``"auto"`` for per-bin adaptive dispatch: each
    Table-I group runs the engine the ``AutotuneCache`` resolved for it
    (static backend seed refined by measured per-bin timings; pass
    ``autotune=`` to scope the cache, default the executor module cache).
    ``gather`` selects how B rows are served: ``"xla"`` (software-only
    baseline), ``"aia"`` (scalar-prefetch Pallas kernels), or ``"auto"``
    (AIA on TPU) — the paper's Fig. 7 ablation axis.
    ``schedule="natural"`` disables the Table-I row grouping (every row
    processed at the global worst-case capacity, natural order) — the
    "without AIA scheduling" software baseline.
    ``mesh`` (a ``jax.Mesh``, e.g. ``launch.mesh.make_spgemm_mesh()``)
    partitions the plan's row ranges across the mesh's devices and runs the
    group programs shard-locally; results are bit-identical to ``mesh=None``.
    ``plan`` amortizes phase 1: a ``GroupPlan`` is used as-is (caller
    guarantees it matches the operands' support), a ``PlanCache`` skips
    ``group_rows`` whenever the operands' sparsity patterns were seen
    before (hits/misses surface in ``executor.cache_stats()``).
    ``pipeline`` selects the executor's sync structure: ``"two_wave"``
    (default) pays one coalesced allocate host sync for all chunks and
    reassembles the CSR on device; ``"legacy"`` is the per-chunk-sync
    NumPy-reassembly reference path (A/B benchmarking).
    ``sizing`` selects how output capacities are found: ``"measured"``
    syncs the uniqueCounts, ``"planned"`` derives sync-free bounds from
    the plan's Alg. 1 IP counts — the executor dispatches the whole call
    with zero blocking host syncs and the host stalls only once, at the
    end, when this façade materializes ``info["nnz_c"]`` (use
    ``executor.execute_plan`` directly for a fully non-blocking device
    handle); ``"auto"`` picks planned for fused engines (``"fused_hash"``)
    and measured otherwise.
    ``operands`` selects the B-side placement under ``mesh=``: ``"auto"``
    (default) ships each shard only the footprint-gathered B block its
    work items' A-support touches (full replica when a shard's footprint
    covers ≥ ~70% of B's rows); ``"footprint"``/``"replicate"`` force
    either path — all bit-identical, with the comm volume surfaced in
    ``executor.cache_stats()``.
    ``operand_cache`` scopes the B-side placement cache (``None`` = the
    executor's module cache); the serving layer passes a per-tenant
    instance so placements are quota'd per tenant.
    ``on_budget`` picks what happens when the plan's
    ``estimated_device_bytes`` exceeds ``executor.set_device_budget``:
    ``"error"`` (default) raises ``DeviceBudgetExceeded``, ``"stream"``
    degrades gracefully — the call transparently re-routes through
    ``spgemm_streamed`` with ``tile_rows`` auto-derived so every tile
    fits the budget, bit-identical to the monolithic result
    (``cache_stats()['budget_degradations']`` counts the re-routes; see
    docs/resilience.md).  Inert when no budget is configured.
    """
    assert a.n_cols == b.n_rows, (a.shape, b.shape)
    with jax.profiler.TraceAnnotation("spgemm"):
        engine = executor.resolve_engine(engine, method)
        on_budget = executor.resolve_on_budget(on_budget)
        # ---- Phase 1: row grouping (one host sync, amortized via ``plan``)
        plan = _resolve_plan(a, b, plan)
        run_plan = plan
        if schedule == "natural":
            run_plan = executor.ungrouped_plan(plan)
        budget = executor.device_budget()
        if on_budget == "stream" and budget is not None:
            itemsize = np.dtype(np.asarray(a.data).dtype).itemsize
            if executor.estimated_device_bytes(plan, itemsize) > budget:
                return _degrade_to_stream(
                    a, b, plan, run_plan, itemsize, method=method,
                    row_chunk=row_chunk, schedule=schedule, engine=engine,
                    gather=gather, mesh=mesh, pipeline=pipeline,
                    sizing=sizing, autotune=autotune, operands=operands,
                    operand_cache=operand_cache)
        # ---- Phases 2+3: compiled group pipeline + device reassembly ----
        with jax.profiler.TraceAnnotation("spgemm.execute"):
            c, nnz = executor.execute_plan(
                a, b, run_plan, engine=engine, gather=gather,
                row_chunk=row_chunk, mesh=mesh, pipeline=pipeline,
                sizing=sizing, autotune=autotune, operands=operands,
                operand_cache=operand_cache,
            )
        info = spgemm_info(a, b, run_plan, nnz, mesh=mesh)
        return SpGEMMResult(c=c, plan=run_plan, info=info)


def spgemm_info(a: CSR, b: CSR, plan: GroupPlan, nnz_c: int,
                mesh=None) -> Dict[str, float]:
    """Hardware-independent counters of one product, as ``SpGEMMResult.info``
    carries them.  ``nnz_c`` is a device scalar when the call sized its
    output on the device (``sizing="planned"``); reading it here waits for
    the product."""
    with jax.profiler.TraceAnnotation("spgemm.info"):
        total_ip = plan.total_ip
        nnz_c = int(nnz_c)
        return {
            "n_shards": 1 if mesh is None else int(np.prod(
                np.asarray(mesh.devices).shape)),
            "nnz_c": nnz_c,
            "intermediate_products": int(total_ip),
            "flops": 2.0 * total_ip,  # the paper's FLOP definition (§VI)
            "compression_ratio": float(total_ip) / max(nnz_c, 1),
            "group_sizes": list(plan.group_sizes),
            "max_ip": plan.max_ip,
        }


def _degrade_to_stream(a, b, plan, run_plan, itemsize, *, method, row_chunk,
                       schedule, engine, gather, mesh, pipeline, sizing,
                       autotune, operands, operand_cache) -> SpGEMMResult:
    """``on_budget="stream"``'s graceful-degradation path (docs/resilience.md).

    The monolithic plan's estimate exceeds the device budget, so the call
    re-routes through ``spgemm_streamed`` with the largest ``tile_rows``
    whose worst row-block tile still fits (``executor.
    derive_degradation_tile_rows``) — bit-identical to the monolithic
    result, just with a tiled memory envelope.  The returned
    ``SpGEMMResult`` keeps the monolithic ``run_plan`` (it is still the
    pattern's reusable plan) and marks ``info`` with ``degraded_to_stream``
    plus the streamed lane's tile counters.
    """
    tile_rows = executor.derive_degradation_tile_rows(
        plan, a.n_rows, itemsize)
    executor._RESILIENCE_STATS["budget_degradations"] += 1
    sres = spgemm_streamed(
        a, b, tile_rows=tile_rows, method=method, row_chunk=row_chunk,
        schedule=schedule, engine=engine, gather=gather, mesh=mesh,
        pipeline=pipeline, sizing=sizing, autotune=autotune,
        operands=operands, operand_cache=operand_cache)
    info = dict(sres.info)
    info["degraded_to_stream"] = 1
    return SpGEMMResult(c=sres.c, plan=run_plan, info=info)


# ---------------------------------------------------------------------------
# Streamed (out-of-core) SpGEMM over row-block tiles of A
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpGEMMStreamResult:
    """Streamed product: the merged CSR ``c`` plus ``info`` extended with
    the lane's tile counters (``n_tiles``, resolved ``tile_rows`` /
    ``prefetch``, ``max_tile_ip``).  There is no single ``plan`` field:
    each row-block tile executed its own ``GroupPlan``, served and
    retained by the lane's ``PlanCache`` (pass ``plan=`` to keep it across
    calls and iterations)."""

    c: CSR
    info: Dict[str, float]


def spgemm_streamed(
    a: CSR,
    b: CSR,
    *,
    tile_rows: Optional[int] = None,
    prefetch: int = 2,
    method: Optional[Literal["hash", "sort"]] = None,
    row_chunk: int = 4096,
    schedule: Literal["grouped", "natural"] = "grouped",
    engine: Optional[str] = None,
    gather: executor.Gather = "auto",
    mesh=None,
    plan: Optional[PlanCache] = None,
    pipeline: executor.Pipeline = "two_wave",
    sizing: executor.Sizing = "auto",
    autotune: Optional[executor.AutotuneCache] = None,
    operands: executor.Operands = "auto",
    operand_cache: Optional[executor.OperandCache] = None,
) -> SpGEMMStreamResult:
    """C = A @ B out-of-core: stream A through the pipeline in row-block
    tiles instead of allocating the whole product's working set at once.

    A is sliced into ``tile_rows`` row blocks on the host; each tile is
    staged host→device asynchronously, planned through the fingerprint-
    keyed ``PlanCache`` (tile patterns repeat across MCL/GNN iterations,
    so planning amortizes exactly like the monolithic ``plan=`` path), run
    through the same compiled pipeline, and merged back on the host by the
    sharded epilogue's destination-mapped segment scatter — a tile is just
    another segment.  The merged result is **bit-identical** to
    ``spgemm`` for every engine × gather × pipeline combination; what
    changes is the memory envelope: the device holds only B, ``prefetch``
    staged tiles of A, and one tile's intermediates at a time (see
    docs/streaming.md for the peak-bytes model), which is how a graph
    whose monolithic plan exceeds ``executor.set_device_budget`` still
    completes.

    ``tile_rows`` (default ``executor.DEFAULT_TILE_ROWS``) sets the tile
    height; ``tile_rows >= n_rows(A)`` collapses to a single monolithic
    tile.  ``prefetch`` (default 2: double buffering) bounds the tiles in
    flight — tile *k+1*'s H2D transfer overlaps tile *k*'s compute, and
    ``cache_stats()['prefetch_overlap_hits']`` counts the overlaps
    actually achieved (``tiles_streamed`` / ``tile_bytes_h2d`` accumulate
    alongside).  ``plan`` must be a ``PlanCache`` (or None for a
    call-local one): the lane plans per tile, so a single ``GroupPlan``
    cannot apply.  Every other knob means exactly what it means for
    ``spgemm``, applied per tile.
    """
    assert a.n_cols == b.n_rows, (a.shape, b.shape)
    engine = executor.resolve_engine(engine, method)
    # validate the streaming knobs at entry, like every other knob
    executor.resolve_tile_rows(tile_rows)
    executor.resolve_prefetch(prefetch)
    if plan is not None and not isinstance(plan, PlanCache):
        raise TypeError(
            "spgemm_streamed plans per tile, so plan= must be a PlanCache "
            f"(or None for a call-local cache); got {type(plan)!r}")
    c, nnz, stream = executor.execute_plan_streamed(
        a, b, tile_rows=tile_rows, prefetch=prefetch, plan=plan,
        engine=engine, gather=gather, row_chunk=row_chunk,
        schedule=schedule, mesh=mesh, pipeline=pipeline, sizing=sizing,
        autotune=autotune, operands=operands, operand_cache=operand_cache,
    )
    total_ip = stream["total_ip"]
    info = {
        "n_shards": 1 if mesh is None else int(np.prod(
            np.asarray(mesh.devices).shape)),
        "nnz_c": int(nnz),
        "intermediate_products": int(total_ip),
        "flops": 2.0 * total_ip,
        "compression_ratio": float(total_ip) / max(nnz, 1),
        **stream,
    }
    return SpGEMMStreamResult(c=c, info=info)


# ---------------------------------------------------------------------------
# Batched SpGEMM over same-pattern operands
# ---------------------------------------------------------------------------

def _as_members(x, what: str) -> List[CSR]:
    if isinstance(x, CSR):
        return [x]
    members = list(x)
    if not members:
        raise ValueError(f"{what} must contain at least one matrix")
    return members


def _require_same_pattern(mats: List[CSR], what: str) -> None:
    t = mats[0]
    t_indptr = None
    for i, m in enumerate(mats[1:], 1):
        if (m.shape == t.shape and m.indptr is t.indptr
                and m.indices is t.indices):
            continue  # shared structure arrays (e.g. reweighted members)
        if t_indptr is None:
            t_indptr = np.asarray(t.indptr)
            nnz = int(t_indptr[-1])
            t_indices = np.asarray(t.indices)[:nnz]
        if (m.shape != t.shape
                or not np.array_equal(np.asarray(m.indptr), t_indptr)
                or not np.array_equal(np.asarray(m.indices)[:nnz], t_indices)):
            raise ValueError(
                f"{what}[{i}] does not share {what}[0]'s sparsity pattern; "
                "spgemm_batched requires structure-identical operands "
                "(values may differ)")


def _stack_values(mats: List[CSR], template: CSR, batch: int) -> np.ndarray:
    """(batch, capacity) value stack aligned to the template's slots."""
    cap = int(template.indices.shape[0])
    nnz = int(np.asarray(template.indptr)[-1])
    out = np.zeros((batch, cap), np.asarray(template.data).dtype)
    for i in range(batch):
        m = mats[i % len(mats)]  # len 1 broadcasts
        out[i, :nnz] = np.asarray(m.data)[:nnz]
    return out


def spgemm_batched(
    a_batch: Union[CSR, Sequence[CSR]],
    b_batch: Union[CSR, Sequence[CSR]],
    method: Optional[Literal["hash", "sort"]] = None,
    row_chunk: int = 4096,
    schedule: Literal["grouped", "natural"] = "grouped",
    engine: Optional[str] = None,
    gather: executor.Gather = "auto",
    mesh=None,
    plan: PlanLike = None,
    pipeline: executor.Pipeline = "two_wave",
    sizing: executor.Sizing = "auto",
    autotune: Optional[executor.AutotuneCache] = None,
    operands: executor.Operands = "auto",
    operand_cache: Optional[executor.OperandCache] = None,
) -> SpGEMMBatchResult:
    """``cs[i] = a_batch[i] @ b_batch[i]`` for same-pattern operand batches.

    Either side may be a single ``CSR`` (its values are shared by every
    batch member) or a sequence of CSRs that all share one sparsity pattern
    (values free to differ) — the GNN mini-batch / iterative-reweighting
    regime.  The plan runs **once** for the whole batch; enumerate keys,
    allocation host syncs, output structure, and reassembly offsets are all
    amortized, and only the value streams are vmapped.  Results are
    bit-identical to looping ``spgemm`` over the members, for every
    engine × gather combination, single- and multi-device (``mesh=``).
    ``sizing`` mirrors ``spgemm``: planned (the fused-engine default)
    sizes the whole batch from Alg. 1 bounds with zero blocking syncs.
    ``operand_cache`` scopes the B-side placement cache as in ``spgemm``.
    """
    a_members = _as_members(a_batch, "a_batch")
    b_members = _as_members(b_batch, "b_batch")
    batch = max(len(a_members), len(b_members))
    if len(a_members) not in (1, batch) or len(b_members) not in (1, batch):
        raise ValueError(
            f"batch mismatch: {len(a_members)} A members vs "
            f"{len(b_members)} B members")
    a, b = a_members[0], b_members[0]
    assert a.n_cols == b.n_rows, (a.shape, b.shape)
    engine = executor.resolve_engine(engine, method)
    _require_same_pattern(a_members, "a_batch")
    _require_same_pattern(b_members, "b_batch")

    plan = _resolve_plan(a, b, plan)
    run_plan = plan
    if schedule == "natural":
        run_plan = executor.ungrouped_plan(plan)

    a_data = _stack_values(a_members, a, batch)
    b_data = None if len(b_members) == 1 else _stack_values(b_members, b, batch)
    indptr, indices, data_batch, nnz = executor.execute_plan_batched(
        a, b, a_data, b_data, run_plan, engine=engine, gather=gather,
        row_chunk=row_chunk, mesh=mesh, pipeline=pipeline, sizing=sizing,
        autotune=autotune, operands=operands, operand_cache=operand_cache,
    )
    indptr_j = jnp.asarray(indptr)
    indices_j = jnp.asarray(indices)
    shape = (a.n_rows, b.n_cols)
    cs = [CSR(indptr_j, indices_j, jnp.asarray(data_batch[i]), shape)
          for i in range(batch)]
    info = spgemm_info(a, b, run_plan, nnz, mesh=mesh)
    info["batch"] = batch
    return SpGEMMBatchResult(cs=cs, plan=run_plan, info=info)


# ---------------------------------------------------------------------------
# Fully-jitted fixed-capacity variant (for scan/training graphs)
# ---------------------------------------------------------------------------

def spgemm_ell_fixed(a: ELL, b: ELL, out_cap: int, engine: str = "sort") -> ELL:
    """C = A @ B entirely in-graph: single group, static caps.

    Row capacity of C is ``out_cap`` (entries beyond it are dropped — size it
    from Algorithm-1 IP bounds).  Suitable inside ``lax.scan`` (MCL) and
    model forward passes.  The engine is resolved through the executor
    registry; both registered engines are jit/scan-compatible.
    """
    engine = executor.resolve_engine(engine)
    if engine == executor.AUTO_ENGINE:
        raise ValueError(
            "spgemm_ell_fixed runs a single fixed-capacity group, so there "
            "are no Table-I bins for engine='auto' to dispatch over; pick a "
            f"concrete engine: {', '.join(executor.available_engines())}")
    keys, vals = phases.enumerate_products(
        jnp.asarray(a.indices), jnp.asarray(a.data), b.indices, b.data
    )
    eng = executor.get_engine(engine)
    cols, out_vals, _ = eng.accumulate(keys, vals, out_cap, out_cap)
    return ELL(cols, out_vals, (a.shape[0], b.shape[1]))
