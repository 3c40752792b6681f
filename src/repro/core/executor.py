"""Plan-compiled SpGEMM executor — the group pipeline behind ``spgemm()``.

The row-grouping phase (``core.grouping``) produces a ``GroupPlan``; this
module *compiles* that plan into a small number of cached, jitted per-group
programs and runs the whole allocate → accumulate → reassemble flow without
any per-row Python.  This is the OpSparse move (fuse setup/allocation into
batched device passes) combined with Nagasaka-style per-bin kernel dispatch:
each Table-I group becomes one statically-shaped program, dispatched at most
``ceil(group_size / row_chunk)`` times.

Three pluggable axes, each resolved per group:

* **engine** — the allocation/accumulation pair.  ``"hash"`` is the paper's
  Algorithm 2/3/5 linear-probing table (vmapped across rows); ``"sort"`` is
  the TPU-vectorized sort + segment-sum engine.  Both are registered in
  ``ENGINES`` behind one interface, so capacity policy and out-cap trimming
  live here instead of being duplicated in ``spgemm()``.
* **gather** — how rows of B are fetched for the two-level indirection
  ``b_ell[cols_A]``.  ``"xla"`` is a plain ``jnp`` take; ``"aia"`` routes
  through the SMEM-indexed DMA kernels in ``kernels.aia_gather`` (the
  paper's AIA ranged indirect access), auto-selecting compiled vs interpret
  mode from the JAX backend.  ``"auto"`` picks ``"aia"`` on TPU and
  ``"xla"`` elsewhere — the paper's software-only vs AIA ablation (Fig. 7)
  is therefore a one-flag switch.
* **schedule** — ``"grouped"`` (Table-I binning) vs ``"natural"`` (one
  group, worst-case capacity; the "without AIA scheduling" baseline).

Per group-chunk the executor runs three cached programs — *enumerate*
(A-row gather → B-row gather → intermediate products; output stays on
device), *allocate* (Algorithms 2/3: uniqueCount), and *accumulate*
(Algorithm 5 on the same device-resident keys) — plus a fourth, the
*scatter* epilogue that reassembles the CSR on device.  Programs live in a
module-level cache keyed on every static quantity that shapes their trace:
``(padded_rows, a_cap, kb_cap, table_cap, out_cap, engine, gather,
dtype)``.  ``a_cap``/``kb_cap`` stay exact (their product is the sort
engine's dominant cost — rounding it up is superlinearly expensive) while
``out_cap`` and the epilogue's total-nnz capacity are pow2-quantized and
row chunks are padded to a fixed quantum, so iterative workloads (MCL
expansion, GNN layers) hit the cache instead of re-tracing;
``cache_stats()`` exposes hit/miss counters for tests and benchmarks.

**Two-wave pipelining**: the blocking point of the whole flow is the
allocate sizing — the host must learn uniqueCount before it can pick
``out_cap``.  Instead of paying that sync once per group-chunk (which
serializes multi-chunk and multi-shard runs on the host exactly where the
paper's AIA pipeline overlaps memory traffic with compute), wave 1
dispatches *every* chunk's enumerate + allocate programs across all shards
without syncing, then one coalesced ``jax.block_until_ready`` over the
stacked uniqueCounts sizes every ``out_cap`` at once; wave 2 runs
accumulate on the already-device-resident keys.  ``cache_stats()`` reports
``host_sync_count`` — exactly one per ``execute_plan`` call on this path,
and CI gates on it.  ``pipeline="legacy"`` keeps the per-chunk-sync
reference path for A/B benchmarks and equivalence tests.

**Fused single-pass engine + sync-free sizing**: the paper's hash flow
forms intermediate products and inserts them into the table in *one pass*
over A's row — ``engine="fused_hash"`` restores exactly that: one cached
program per group-chunk fusing gather → product formation → linear-probe
insertion (Pallas Algorithm-4 kernel on TPU, the vmapped scan engine
elsewhere), so the enumerate key/value stream never becomes an HBM-resident
buffer handed between programs and the allocate pass disappears entirely.
What allocate used to buy — output sizing — comes for free from phase 1:
uniqueCount ≤ min(IP, n_cols) per row, and ``GroupPlan.row_ip`` carries the
Alg. 1 counts, so ``sizing="planned"`` (the fused default) picks every
``out_cap`` and the epilogue capacity from pow2-quantized host bounds and
assembles the int32 indptr *on device* — ``host_sync_count`` stays at
**zero** for the whole call, with ``nnz`` returned as a device scalar that
blocks only at caller materialization.  (The ``spgemm()`` façade
materializes it when it builds ``info`` — but only *after* every program
in the call has been dispatched, so the host never stalls mid-pipeline
the way the measured sizing sync does; callers that want a fully
non-blocking handle use ``execute_plan`` directly.)  ``sizing="measured"`` is the
escape hatch for pathologically overlapping supports where the IP bound is
loose (it keeps the one coalesced uniqueCount sync and exact capacities);
``"auto"`` resolves to planned for fused engines and measured otherwise.

**Sharded scatter epilogue**: with more than one shard, chunk outputs no
longer stream through the lead device one padded block at a time.  Each
chunk packs densely into its shard's *local* CSR segment on the shard
device together with a destination map (``phases.reassemble_segment``, a
running-offset donated-buffer update), and the merge device applies one
destination-mapped scatter per shard (``phases.merge_segments``) — the
reassembly compute parallelizes across shards and merge traffic is
``n_shards`` compact nnz-sized transfers.  Bit-exact vs the direct
single-device epilogue: shard row sets are disjoint, so every final slot
is written by exactly one segment.

CSR reassembly is a vectorized inverse-permutation scatter.  The two-wave
path runs it as a jitted device epilogue (``phases.reassemble_device``):
flat destination offsets derive from the (host) indptr, and each chunk's
rows are scattered into pow2-quantized int32 ``indices`` / ``data``
buffers *on device* — shard outputs merge device-side and ``np``
conversion happens only when the caller materializes the CSR (nnz beyond
int32 raises instead of silently downcasting).  The legacy path keeps the
host-side NumPy scatter.

**Sharded multi-device execution** (``mesh=``): the paper's AIA scheduling
partitions SpGEMM work so each memory stack serves *local* indirection
traffic; ``execute_plan(..., mesh=...)`` applies the same idea across a
``jax.Mesh``.  The plan is split into group-chunk work items
(``partition_plan``), items are assigned round-robin *within each group* so
every shard gets a balanced mix of Table-I bins, the A/B operands are
replicated onto every shard device once per call (the all-gather analogue —
each "stack" holds the B rows its indirection touches), and each item's
enumerate/allocate/accumulate programs run shard-locally on its assigned
device.  Shard outputs merge through the same inverse-permutation
reassembly, so the result is bit-identical to the single-device path for
every engine × gather combination (per-row results never depend on which
shard computed them).  The program cache is shared across shards — one
Python-level signature entry serves every device, and jax's per-device jit
cache keeps each shard's executable warm across iterations.

**Amortization layer** (this module's third concern, after compiling and
sharding): the planning cost — Algorithm 1 IP counting plus Table-I
binning — depends only on the operands' *sparsity patterns*, and the two
headline workloads repeat patterns constantly: MCL re-multiplies the same
support for dozens of iterations once the clustering stabilizes, and GNN
mini-batch sampling produces many matrices that share one structure with
different values.  Two mechanisms exploit that:

* ``PlanCache`` — a fingerprint-keyed (``pattern_fingerprint``: blake2b of
  shape + indptr + occupied indices) map from operand sparsity patterns to
  ``GroupPlan``s.  ``spgemm(..., plan=cache)`` skips ``group_rows``
  entirely on a hit; ``plan_hits``/``plan_misses`` counters are folded
  into ``cache_stats()``.  Shard assignment is memoized the same way
  (``partition_plan`` results keyed on plan content + chunking + shard
  count), so under ``mesh=`` a reused plan also reuses its work-item
  partition.
* ``execute_plan_batched`` — runs the plan once for a whole batch of
  same-pattern operands (values differ, structure shared).  The key
  tensor, allocation sizing (the coalesced host sync), output structure,
  and reassembly offsets are computed once per chunk for the entire batch;
  only the value streams are vmapped through the cached accumulate
  programs.  Under ``mesh=`` the batch rides the same shard assignment as
  the single-matrix path, and results are bit-identical to a per-matrix
  Python loop for every engine × gather combination.
* ``OperandCache`` — B's replicated ELL buffers (conversion + per-shard
  placement) keyed on the operand's identity and the device set, shared
  across batched/iterative calls instead of re-replicated per call;
  ``operand_hits``/``operand_misses`` in ``cache_stats()``.
* ``AutotuneCache`` — ``engine="auto"``'s measured per-bin engine
  assignments, keyed like ``PlanCache`` plus backend + bin signature.
  Each unconverged call measures one candidate per non-empty Table-I bin
  (a timed bin-restricted sub-execution); converged calls serve the
  frozen assignment with zero re-measurement.
  ``autotune_hits``/``autotune_misses`` in ``cache_stats()``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import time
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Literal, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults, phases
from repro.core.grouping import GroupPlan, group_rows, support_footprint
from repro.launch.sharding import (
    SHARDING_STATS, merge_device, place_operand_block, replicate_to,
    shard_devices, stage_tile)
from repro.sparse.formats import CSR, ELL, csr_to_ell

Gather = Literal["auto", "xla", "aia"]
Schedule = Literal["grouped", "natural"]
Pipeline = Literal["two_wave", "legacy"]
Sizing = Literal["auto", "planned", "measured"]
Operands = Literal["auto", "footprint", "replicate"]
OnBudget = Literal["error", "stream"]

# A shard whose B-row footprint covers at least this fraction of B's rows
# takes the full-replication fast path under ``operands="auto"``: the
# sub-ELL slice + remap would save little and costs an extra indirection.
FOOTPRINT_THRESHOLD = 0.7


def resolve_operands(operands: Operands) -> str:
    """Validate the ``operands=`` placement policy.

    ``"auto"`` (default) places footprint-gathered B blocks on shards whose
    footprint stays under ``FOOTPRINT_THRESHOLD`` of B's rows (full replicas
    elsewhere, and always on a single shard); ``"footprint"`` forces the
    block path on every shard; ``"replicate"`` forces the pre-footprint
    full replication (the A/B baseline the comm-volume probes diff against).
    """
    if operands not in ("auto", "footprint", "replicate"):
        raise ValueError(
            f"unknown operands policy {operands!r}; valid choices: "
            "'auto', 'footprint', 'replicate'")
    return operands


# Streaming-lane defaults (docs/streaming.md): rows per A row-block tile,
# and how many tiles may be resident on the device at once (1 = no overlap,
# 2 = classic double buffering — tile k+1's H2D transfer overlaps tile k's
# compute).
DEFAULT_TILE_ROWS = 4096
DEFAULT_PREFETCH = 2


def resolve_tile_rows(tile_rows) -> int:
    """Validate the streamed lane's ``tile_rows=`` knob (rows per tile).

    ``None`` resolves to ``DEFAULT_TILE_ROWS``.  Any positive integer is
    valid: ``tile_rows >= n_rows(A)`` simply collapses the schedule to a
    single tile (the monolithic shape), smaller values trade per-tile
    planning/launch overhead for a smaller peak device working set.
    """
    if tile_rows is None:
        return DEFAULT_TILE_ROWS
    if isinstance(tile_rows, bool) or not isinstance(tile_rows, (int, np.integer)):
        raise ValueError(
            f"tile_rows must be a positive int (or None for the default "
            f"{DEFAULT_TILE_ROWS}); got {tile_rows!r}")
    if int(tile_rows) < 1:
        raise ValueError(f"tile_rows must be >= 1; got {int(tile_rows)}")
    return int(tile_rows)


def resolve_prefetch(prefetch) -> int:
    """Validate the streamed lane's ``prefetch=`` knob (tiles in flight).

    ``prefetch`` bounds how many staged tiles may be device-resident at
    once: ``1`` disables overlap (stage, compute, merge, repeat), ``2``
    (default) double-buffers so tile *k+1*'s host→device transfer overlaps
    tile *k*'s compute, larger values deepen the pipeline at the cost of
    ``prefetch`` tiles of operand memory.
    """
    if prefetch is None:
        return DEFAULT_PREFETCH
    if isinstance(prefetch, bool) or not isinstance(prefetch, (int, np.integer)):
        raise ValueError(
            f"prefetch must be a positive int; got {prefetch!r}")
    if int(prefetch) < 1:
        raise ValueError(f"prefetch must be >= 1; got {int(prefetch)}")
    return int(prefetch)


# ---------------------------------------------------------------------------
# Device-memory budget — the streamed lane's raison d'être made testable
# ---------------------------------------------------------------------------

# Optional cap (bytes) on the estimated device working set a single
# execute_plan call may allocate.  ``None`` (default) disables the check.
_DEVICE_BUDGET = {"bytes": None}


class DeviceBudgetExceeded(RuntimeError):
    """A plan's estimated device working set exceeds ``set_device_budget``.

    Raised by ``execute_plan`` before any device allocation happens, so an
    over-memory monolithic call fails fast and cleanly; the streamed lane
    (``execute_plan_streamed``) runs the same check per *tile*, which is
    how a graph that exceeds the budget monolithically still completes —
    pick ``tile_rows`` small enough that every tile's estimate fits.
    """


def set_device_budget(nbytes: Optional[int]) -> None:
    """Set (or clear, with ``None``) the device working-set budget in bytes.

    The budget models the accelerator's memory ceiling: ``execute_plan``
    raises ``DeviceBudgetExceeded`` when ``estimated_device_bytes`` of the
    plan it was handed exceeds it.  Tests and the over-memory MCL path use
    this to make "does not fit" an observable, hardware-independent event.
    """
    _DEVICE_BUDGET["bytes"] = None if nbytes is None else int(nbytes)


def device_budget() -> Optional[int]:
    """The configured device working-set budget in bytes (None = off)."""
    return _DEVICE_BUDGET["bytes"]


def estimated_device_bytes(plan: "GroupPlan", itemsize: int) -> int:
    """Upper-bound estimate of a plan's device working set, in bytes.

    The memory model documented in docs/streaming.md: the two-wave
    pipeline keeps every chunk's enumerated key/value streams device-
    resident until wave 2 consumes them, so the peak is dominated by the
    intermediate products — ``total_ip × (4 + itemsize)`` bytes (an int32
    key plus one value per product).  Operands and the output CSR are
    deliberately excluded: they are shared across tiles (B) or bounded by
    the same IP term.  For the streamed lane the bound applies per tile,
    so it shrinks roughly linearly with ``tile_rows``.
    """
    return int(plan.total_ip) * (4 + int(itemsize))


def resolve_on_budget(on_budget: OnBudget) -> str:
    """Validate the ``on_budget=`` over-budget policy (docs/resilience.md).

    Chooses what a monolithic ``spgemm``/``mcl`` call does when
    ``estimated_device_bytes`` of its plan exceeds ``set_device_budget``:
    ``"error"`` (default, the compatible behaviour) raises
    ``DeviceBudgetExceeded``; ``"stream"`` degrades gracefully — the call
    transparently re-runs through ``spgemm_streamed`` with ``tile_rows``
    derived so every tile fits the budget, bit-identical to the
    monolithic result.  With no budget configured the knob is inert.
    """
    if on_budget not in ("error", "stream"):
        raise ValueError(
            f"unknown on_budget policy {on_budget!r}; valid choices: "
            "'error', 'stream'")
    return on_budget


def derive_degradation_tile_rows(plan: "GroupPlan", n_rows: int,
                                 itemsize: int) -> int:
    """Largest pow2 ``tile_rows`` whose worst row-block tile fits the budget.

    The ``on_budget="stream"`` degradation path needs a ``tile_rows`` such
    that *every* contiguous row-block tile's intermediate-product estimate
    (same memory model as ``estimated_device_bytes``, applied to the
    tile's rows) stays within ``set_device_budget``.  Starting from
    ``n_rows`` and halving, the first size whose worst tile fits wins —
    the fewest tiles, hence the least streaming overhead.  Raises
    ``DeviceBudgetExceeded`` when even a single row exceeds the budget
    (no tiling can help), ``ValueError`` with no budget configured.
    """
    budget = _DEVICE_BUDGET["bytes"]
    if budget is None:
        raise ValueError(
            "derive_degradation_tile_rows needs a device budget; call "
            "set_device_budget first")
    row_bytes = np.asarray(plan.row_ip, dtype=np.int64) * (4 + int(itemsize))
    if row_bytes.size != n_rows:
        raise ValueError(
            f"plan has {row_bytes.size} row_ip entries but n_rows={n_rows}")
    worst_row = int(row_bytes.max()) if row_bytes.size else 0
    if worst_row > budget:
        raise DeviceBudgetExceeded(
            f"a single row's intermediate products need ~{worst_row} device "
            f"bytes but the configured device budget is {budget}; no "
            "tile_rows can degrade this call — raise the budget")
    prefix = np.concatenate(([0], np.cumsum(row_bytes)))

    def worst_tile(t: int) -> int:
        starts = np.arange(0, n_rows, t)
        ends = np.minimum(starts + t, n_rows)
        return int((prefix[ends] - prefix[starts]).max()) if starts.size else 0

    t = max(next_pow2(max(n_rows, 1)), 1)
    while t > 1 and worst_tile(t) > budget:
        t //= 2
    return t


# Rows per program dispatch are padded to a multiple of this so repeated
# calls with slightly different group sizes reuse compiled programs.
ROW_QUANTUM = 8


def next_pow2(x: int) -> int:
    """Smallest power of two >= ``x`` (and >= 1) — the capacity quantum
    that keeps compiled-program signatures coarse enough to reuse."""
    return 1 << int(np.ceil(np.log2(max(int(x), 1))))


# ---------------------------------------------------------------------------
# Engine registry — hash and sort behind one interface
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Engine:
    """One allocation/accumulation engine (paper phases 2 + 3).

    ``allocate(keys, table_cap)`` → per-row uniqueCount (Algorithms 2/3).
    ``accumulate(keys, vals, table_cap, out_cap)`` → (cols, vals, counts)
    with rows column-sorted and trimmed/padded to ``out_cap`` (Algorithm 5).

    ``fused=True`` marks a single-pass engine: under ``sizing="planned"``
    the executor compiles one fused program per group-chunk (gather →
    product formation → table insertion, no allocate pass and no
    materialized key/value stream between programs) and sizes ``out_cap``
    from the plan's Alg. 1 IP bounds instead of a uniqueCount host sync.
    The ``allocate``/``accumulate`` pair is still required — it serves the
    ``sizing="measured"`` escape hatch and the legacy pipeline.
    """

    name: str
    allocate: Callable[[jax.Array, int], jax.Array]
    accumulate: Callable[[jax.Array, jax.Array, int, int],
                         Tuple[jax.Array, jax.Array, jax.Array]]
    fused: bool = False


ENGINES: Dict[str, Engine] = {}


def register_engine(engine: Engine) -> Engine:
    """Add an ``Engine`` to the registry (keyed by name) and return it."""
    ENGINES[engine.name] = engine
    return engine


def get_engine(name: str) -> Engine:
    """Look up a registered engine by name (ValueError when unknown)."""
    try:
        return ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(ENGINES)}"
        ) from None


def available_engines() -> Tuple[str, ...]:
    """Sorted names of every registered engine (the ``engine=`` choices
    besides ``"auto"``)."""
    return tuple(sorted(ENGINES))


AUTO_ENGINE = "auto"


def resolve_engine(engine: Optional[str] = None,
                   method: Optional[str] = None) -> str:
    """Validate an ``engine=`` value everywhere it is threaded.

    Accepts any registered engine name plus ``"auto"`` (per-bin adaptive
    dispatch: the executor resolves one engine per Table-I group from the
    static heuristics + the ``AutotuneCache``).  ``method`` is the legacy
    alias kept by the ``spgemm`` façade; ``None`` falls back to
    ``method or "sort"``.  A typo raises immediately with the full list of
    valid choices instead of surfacing as a deep ``get_engine`` failure.
    """
    if engine is None:
        engine = method or "sort"
    elif method is not None and method != engine:
        raise ValueError(
            f"conflicting method={method!r} (legacy alias) and "
            f"engine={engine!r}")
    if engine != AUTO_ENGINE and engine not in ENGINES:
        raise ValueError(
            f"unknown engine {engine!r}; valid choices: "
            f"{', '.join(sorted(ENGINES))}, or 'auto' (per-bin adaptive "
            "dispatch)")
    return engine


def static_bin_engines(table_capacities: Sequence[int],
                       backend: Optional[str] = None) -> Tuple[str, ...]:
    """Static bin-size × backend seed for ``engine="auto"``.

    On CPU the CI baseline has the vectorized sort engine ahead (selfprod:
    sort 67 ms vs hash 500 ms / fused_hash 297 ms, CPU wall times), so
    every bin starts on ``"sort"`` off-TPU.  On TPU every bin starts on
    ``"fused_hash"`` (the compiled Algorithm-4 kernel) — which engine wins
    each bin on the chip is not measured.  One rule is structural: a bin
    whose table (``table_capacities``, the plan's per-group capacities)
    exceeds the kernel's SMEM budget (``kernels.hash_accum.table_fits``) starts
    on ``"sort"`` instead.  This is only the *starting point* — the
    ``AutotuneCache`` measures each bin's candidates on the live pattern
    and converges to the measured per-bin optimum (nsparse-style adaptive
    accumulator selection, arXiv:1804.01698).
    """
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu":
        return ("sort",) * 4
    from repro.kernels.hash_accum import table_fits

    return tuple("fused_hash" if table_fits(c) else "sort"
                 for c in table_capacities)


def _hash_accumulate(keys, vals, table_cap: int, out_cap: int):
    cols, out_vals, counts = phases.accumulate_hash(keys, vals, table_cap)
    # The table must hold up to ``table_cap`` probes, but uniqueCount never
    # exceeds ``out_cap`` (≥ n_cols bound); trim to the sorted prefix.
    return cols[:, :out_cap], out_vals[:, :out_cap], counts


def _sort_accumulate(keys, vals, table_cap: int, out_cap: int):
    return phases.accumulate_sort(keys, vals, out_cap)


register_engine(Engine("hash", phases.allocate_hash, _hash_accumulate))
register_engine(Engine("sort", lambda k, cap: phases.allocate_sort(k),
                       _sort_accumulate))
# The paper's Alg. 2/3/5 as ONE pass over A's row (the multi-phase flow the
# hash table exists for): gather → product formation → linear-probe insert
# fused into a single cached program per group-chunk.  The allocate/
# accumulate pair below only serves sizing="measured" and pipeline="legacy";
# the planned path never runs them.
register_engine(Engine("fused_hash", phases.allocate_hash, _hash_accumulate,
                       fused=True))


# ---------------------------------------------------------------------------
# Gather backends — how b_ell[cols_A] is served
# ---------------------------------------------------------------------------

def resolve_gather(gather: Gather) -> str:
    """``"auto"`` → AIA kernels on TPU, XLA take elsewhere (Fig. 7 switch).

    Honors the ``REPRO_KERNEL_BACKEND`` override with the same semantics as
    ``kernels.ops.resolve_backend``: ``xla`` forces the software-only take,
    ``pallas``/``interpret`` force the AIA kernels.
    """
    if gather == "auto":
        env = os.environ.get("REPRO_KERNEL_BACKEND")
        if env == "xla":
            return "xla"
        if env in ("pallas", "interpret"):
            return "aia"
        return "aia" if jax.default_backend() == "tpu" else "xla"
    if gather not in ("xla", "aia"):
        raise ValueError(f"unknown gather backend {gather!r}")
    return gather


def ell_width(kb_cap: int, gather: str) -> int:
    """Stored width of B's placed ELL planes.

    The AIA row DMA moves whole 128-lane tiles, so the ``aia`` path places
    B pre-padded to the tile (once per placed operand, in the
    ``OperandCache``) and its programs trim gathered rows back to
    ``kb_cap``; the ``xla`` take reads the exact width."""
    if gather == "aia":
        from repro.kernels.aia_gather import padded_width

        return padded_width(kb_cap)
    return kb_cap


def _gather_b_xla(b_idx, b_val, cols_a, kb):
    safe = jnp.clip(cols_a, 0, b_idx.shape[0] - 1)
    return b_idx[safe][..., :kb], b_val[safe][..., :kb]


def _gather_b_aia(b_idx, b_val, cols_a, kb):
    """B-row gather as the paper's AIA stream (SMEM-indexed DMA kernel).

    ``cols_a`` rows are flattened into one bulk index stream, gathered
    near-memory from the lane-padded planes, reshaped back and trimmed to
    the exact ``kb`` (padding columns hold -1/0, so the trim is exact).
    """
    from repro.kernels.aia_gather import gather_rows_any

    r, a_cap = cols_a.shape
    width = b_idx.shape[1]
    flat = cols_a.reshape(-1)
    bi = gather_rows_any(b_idx, flat).reshape(r, a_cap, width)
    bv = gather_rows_any(b_val, flat).reshape(r, a_cap, width)
    return bi[..., :kb], bv[..., :kb]


GATHERS: Dict[str, Callable] = {"xla": _gather_b_xla, "aia": _gather_b_aia}


def _gather_b_xla_batched(b_idx, b_val_b, cols_a, kb):
    """Batched-value variant: one structural gather, values broadcast."""
    safe = jnp.clip(cols_a, 0, b_idx.shape[0] - 1)
    # (R, a_cap, kb), (B, R, a_cap, kb)
    return b_idx[safe][..., :kb], b_val_b[:, safe][..., :kb]


def _gather_b_aia_batched(b_idx, b_val_b, cols_a, kb):
    """Batched AIA gather: the batch axis folds into the row payload, so a
    single widened DMA stream serves every batch member's B rows — the same
    index stream, amortized (the near-memory analogue of reading one wider
    row instead of B narrow ones)."""
    from repro.kernels.aia_gather import gather_rows_any

    r, a_cap = cols_a.shape
    nb, width = b_idx.shape
    batch = b_val_b.shape[0]
    flat = cols_a.reshape(-1)
    bi = gather_rows_any(b_idx, flat).reshape(r, a_cap, width)
    folded = jnp.transpose(b_val_b, (1, 0, 2)).reshape(nb, batch * width)
    bv = gather_rows_any(folded, flat).reshape(r, a_cap, batch, width)
    return bi[..., :kb], jnp.transpose(bv, (2, 0, 1, 3))[..., :kb]


BATCHED_GATHERS: Dict[str, Callable] = {
    "xla": _gather_b_xla_batched, "aia": _gather_b_aia_batched,
}


# ---------------------------------------------------------------------------
# Output sizing — measured (uniqueCount sync) vs planned (Alg. 1 bounds)
# ---------------------------------------------------------------------------

def _engines_in_use(engine: str, plan=None,
                    group_engines: Optional[Sequence[str]] = None
                    ) -> Tuple[str, ...]:
    """The engine names a call will actually dispatch: the per-bin
    assignment restricted to non-empty groups when one is set, else the
    uniform ``engine=``."""
    if group_engines is None:
        return (engine,)
    sizes = getattr(plan, "group_sizes", None)
    used = tuple(e for g, e in enumerate(group_engines)
                 if sizes is None or sizes[g] > 0)
    return used or (group_engines[0],)


def resolve_sizing(sizing: Sizing, engine: str, plan=None,
                   group_engines: Optional[Sequence[str]] = None) -> str:
    """``"auto"`` → ``"planned"`` for fused engines, ``"measured"``
    otherwise.

    Planned sizing derives every chunk's ``out_cap`` and the epilogue
    capacity from the plan's per-row Alg. 1 IP counts (uniqueCount ≤
    min(IP, n_cols) per row — a bound phase 1 already paid for), so the
    two-wave pipeline dispatches end-to-end with **zero** blocking host
    syncs.  ``"measured"`` is the escape hatch for pathological overlap
    (many duplicate columns per row make the IP bound loose, inflating
    ``out_cap`` and the output buffers): it keeps the single coalesced
    uniqueCount sync and exact capacities.

    With a per-bin assignment (``engine="auto"`` or
    ``plan.group_engines``), the rule applies to every engine the call
    will actually dispatch: planned only when **all** non-empty bins
    resolved to fused engines, measured as soon as any bin picked a
    non-fused one (that bin needs the uniqueCount sync anyway, and the
    coalesced sync sizes every chunk at once).
    """
    if sizing not in ("auto", "planned", "measured"):
        raise ValueError(f"unknown sizing {sizing!r}")
    if sizing == "auto":
        engines = _engines_in_use(engine, plan, group_engines)
        all_fused = all(get_engine(e).fused for e in engines)
        return "planned" if (all_fused
                             and getattr(plan, "row_ip", None) is not None) \
            else "measured"
    if sizing == "planned" and plan is not None \
            and getattr(plan, "row_ip", None) is None:
        raise ValueError(
            "sizing='planned' needs a plan carrying Alg. 1 row IP counts "
            "(GroupPlan.row_ip); re-plan with core.grouping.group_rows")
    return sizing


def chunk_capacity_bounds(plan: GroupPlan, rows: np.ndarray,
                          n_cols: int) -> Tuple[int, int]:
    """(max-unique, total-unique) bounds for one chunk of rows.

    uniqueCount of row r is at most ``min(IP[r], n_cols(B))`` — every
    intermediate product lands on one output column, and there are only
    ``n_cols`` distinct columns.  Both bounds are exact host arithmetic on
    the plan's Alg. 1 counts: no device work, no sync.
    """
    ip = np.asarray(plan.row_ip)[rows].astype(np.int64)
    unique = np.minimum(ip, int(n_cols))
    return int(unique.max(initial=0)), int(unique.sum())


def _planned_out_cap(max_unique: int, table_cap: int, ncol_cap: int) -> int:
    """pow2-quantized chunk output capacity from the plan-derived bound —
    the sync-free mirror of ``_out_cap_from_counts``."""
    return max(min(next_pow2(max(max_unique, 1)), max(table_cap, 1),
                   ncol_cap), 1)


def _fused_kernel_mode(dt: str, table_cap: int) -> str:
    """Algorithm-4 routing inside the fused program: the Pallas kernel
    (compiled on TPU, interpret under ``REPRO_KERNEL_BACKEND=interpret``)
    for float32 streams whose table fits the kernel's SMEM budget
    (``hash_accum.table_fits``), the vmapped scan engine everywhere else
    (the kernel's value plane is float32-only)."""
    from repro.kernels.hash_accum import table_fits

    if dt != np.dtype(np.float32).str or not table_fits(table_cap):
        return "xla"
    from repro.kernels.backend import resolve_backend

    be = resolve_backend("auto")
    return be if be in ("pallas", "interpret") else "xla"


# ---------------------------------------------------------------------------
# Program cache — one jitted program per static-shape signature
# ---------------------------------------------------------------------------

_PROGRAM_CACHE: Dict[tuple, Callable] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}
_PLAN_STATS = {"plan_hits": 0, "plan_misses": 0}
# One increment per *blocking* host synchronization.  The two-wave pipeline
# pays exactly one per execute_plan call (the coalesced allocate sync); the
# legacy pipeline pays one per group-chunk.  CI gates on this.
_SYNC_STATS = {"host_sync_count": 0}
# OperandCache lookups: a hit means the B-side placed ELL buffers were
# served without any re-placement (zero device transfers).  The comm-volume
# counters accumulate at *placement* time (cache misses only):
# ``operand_bytes_placed`` — bytes of B-side buffers (indices + values +
# remap) actually shipped to shard devices; ``operand_rows_footprint`` —
# B rows placed, summed over shards; ``operand_rows_total`` — what full
# replication would have placed (n_shards × n_rows(B)).  CI diffs a
# replicated run against a footprint run and gates on the saving.
_OPERAND_STATS = {"operand_hits": 0, "operand_misses": 0,
                  "operand_bytes_placed": 0, "operand_rows_footprint": 0,
                  "operand_rows_total": 0}
# AutotuneCache lookups for engine="auto": a hit serves a fully-measured
# per-bin assignment with zero re-measurement; a miss covers both the first
# sighting of a (pattern, backend, bin-signature) key and every incremental
# measurement round until the per-bin candidates are exhausted.
_AUTOTUNE_STATS = {"autotune_hits": 0, "autotune_misses": 0}
# Streamed (out-of-core) lane: ``tiles_streamed`` counts row-block tiles
# dispatched through the tile scheduler; ``tile_bytes_h2d`` accumulates the
# bytes of tile operand arrays (indptr + indices + data) staged host→device;
# ``prefetch_overlap_hits`` counts tiles whose staging was issued while an
# earlier tile's compute was still in flight — i.e. transfers the double
# buffering actually overlapped with compute (0 whenever ``prefetch=1``).
_STREAM_STATS = {"tiles_streamed": 0, "tile_bytes_h2d": 0,
                 "prefetch_overlap_hits": 0}
# Resilience layer (docs/resilience.md): ``capacity_retries`` counts
# planned/fused chunks whose device-side overflow flag tripped and were
# re-executed once at measured capacity; ``budget_degradations`` counts
# monolithic calls that ``on_budget="stream"`` transparently re-routed
# through the streamed lane.  Both are 0 on every clean path — any nonzero
# value is a recovery event worth surfacing.  ``sharding_fallbacks`` (owned
# by launch.sharding to avoid a circular import) counts constrain() calls
# that degraded to unconstrained placement outside a mesh context.
_RESILIENCE_STATS = {"capacity_retries": 0, "budget_degradations": 0}


def cache_stats() -> Dict[str, int]:
    """Global executor counters, one flat dict.  Every field:

    * ``hits`` / ``misses`` — jitted-program cache lookups: a hit reuses a
      compiled enumerate/allocate/accumulate/fused/scatter program, a miss
      traces and compiles a new one.
    * ``plan_hits`` / ``plan_misses`` — ``PlanCache`` lookups (every
      instance folds into these): a hit skips Alg. 1 + Table-I binning.
    * ``host_sync_count`` — blocking host synchronizations paid inside the
      pipeline: exactly one per measured two-wave call, zero per
      planned/fused call, one per chunk on ``pipeline="legacy"``.
    * ``operand_hits`` / ``operand_misses`` — B-side placement cache
      lookups (every ``OperandCache`` instance folds into these): a hit
      serves the placed ELL buffers with zero conversions or transfers.
    * ``operand_bytes_placed`` — bytes of B-side buffers (indices + values
      + remap) actually shipped to shard devices, accumulated at placement
      (miss) time.
    * ``operand_rows_footprint`` / ``operand_rows_total`` — B rows placed
      (summed over shards) vs what full replication would have placed
      (``n_shards × n_rows(B)``); their ratio is the comm saving.
    * ``autotune_hits`` / ``autotune_misses`` — ``engine="auto"`` lookups:
      a hit serves a converged per-bin assignment with zero
      re-measurement, a miss covers every round that still measured.
    * ``tiles_streamed`` — row-block tiles dispatched by the streamed
      (out-of-core) lane's tile scheduler.
    * ``tile_bytes_h2d`` — bytes of streamed tile operands (indptr +
      indices + data) staged host→device.
    * ``prefetch_overlap_hits`` — streamed tiles whose staging was issued
      while an earlier tile's compute was still in flight (the double
      buffering actually overlapped; 0 under ``prefetch=1``).
    * ``capacity_retries`` — planned/fused chunks whose device-side
      overflow flag tripped and were re-executed once at measured
      capacity (0 on every clean path; see docs/resilience.md).
    * ``budget_degradations`` — monolithic calls ``on_budget="stream"``
      transparently re-routed through the streamed lane because their
      estimate exceeded the device budget.
    * ``sharding_fallbacks`` — ``constrain()`` calls that degraded to
      unconstrained placement because no mesh context was active.
    """
    return {**_CACHE_STATS, **_PLAN_STATS, **_SYNC_STATS, **_OPERAND_STATS,
            **_AUTOTUNE_STATS, **_STREAM_STATS, **_RESILIENCE_STATS,
            **SHARDING_STATS}


def clear_program_cache() -> None:
    """Drop every executor-level cache and zero the ``cache_stats()``
    counters (tests and benchmarks use this to isolate measurements)."""
    _PROGRAM_CACHE.clear()
    _PARTITION_CACHE.clear()
    _FOOTPRINT_CACHE.clear()
    _OPERAND_CACHE.clear()
    _AUTOTUNE_CACHE.clear()
    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0
    _PLAN_STATS["plan_hits"] = 0
    _PLAN_STATS["plan_misses"] = 0
    _SYNC_STATS["host_sync_count"] = 0
    for k in _OPERAND_STATS:
        _OPERAND_STATS[k] = 0
    _AUTOTUNE_STATS["autotune_hits"] = 0
    _AUTOTUNE_STATS["autotune_misses"] = 0
    for k in _STREAM_STATS:
        _STREAM_STATS[k] = 0
    for k in _RESILIENCE_STATS:
        _RESILIENCE_STATS[k] = 0
    for k in SHARDING_STATS:
        SHARDING_STATS[k] = 0


def _coalesced_sync(arrays: Sequence[jax.Array]) -> List[np.ndarray]:
    """The pipeline's single blocking host sync: every pending device
    computation was already dispatched, so one ``block_until_ready`` over
    the whole list drains them together instead of serializing per chunk."""
    _SYNC_STATS["host_sync_count"] += 1
    arrays = jax.block_until_ready(list(arrays))
    return [np.asarray(x) for x in arrays]


# ---------------------------------------------------------------------------
# Plan cache — amortize Alg. 1 + Table-I binning across same-pattern calls
# ---------------------------------------------------------------------------

def pattern_fingerprint(*mats) -> str:
    """Sparsity-pattern fingerprint of CSR operands: blake2b over shape,
    indptr, and the *occupied* slots of indices.

    Values and capacity padding are deliberately excluded — two matrices
    with the same support but different values (an MCL iteration at
    fixpoint, one mini-batch value set vs another) fingerprint identically,
    while mutating a single column index (same nnz, different support)
    changes the digest.
    """
    h = hashlib.blake2b(digest_size=16)
    for m in mats:
        indptr = np.asarray(m.indptr)
        indices = np.asarray(m.indices)
        nnz = int(indptr[-1])
        h.update(np.asarray(m.shape, np.int64).tobytes())
        h.update(indptr.tobytes())
        h.update(indices[:nnz].tobytes())
    return h.hexdigest()


class PlanCache:
    """Fingerprint-keyed ``GroupPlan`` cache (LRU, bounded).

    ``plan_for(a, b)`` returns the cached plan when the operands' sparsity
    patterns were seen before and runs ``group_rows`` otherwise — the
    OpSparse-style setup-cost amortization for iterative (MCL) and batched
    (GNN sampling) workloads.  Hits/misses are tracked per instance *and*
    folded into the module-level ``cache_stats()`` counters.
    """

    def __init__(self, max_entries: int = 64):
        self.max_entries = max_entries
        self._entries: "OrderedDict[str, GroupPlan]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        """Number of cached plans currently held (bounded by
        ``max_entries``)."""
        return len(self._entries)

    def plan_for(self, a: "CSR", b: "CSR",
                 supplier: Optional[Callable[[], GroupPlan]] = None
                 ) -> GroupPlan:
        """Serve (hit) or build (miss) the plan for ``(a, b)``'s pattern.

        ``supplier`` overrides how a miss is filled: instead of running
        ``group_rows``, the cache stores whatever the callable returns.
        This is the multi-tenant scoping hook — when one coalesced dispatch
        spans several tenants' caches, the first cache computes the plan
        and the others *account* the same plan against their own quota
        without re-planning (``serve.spgemm_service`` uses exactly this).
        A supplier-filled miss still counts as a miss.
        """
        key = pattern_fingerprint(a, b)
        plan = self._entries.get(key)
        if plan is None:
            self.misses += 1
            _PLAN_STATS["plan_misses"] += 1
            plan = group_rows(a, b) if supplier is None else supplier()
            self._entries[key] = plan
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
        else:
            self.hits += 1
            _PLAN_STATS["plan_hits"] += 1
            self._entries.move_to_end(key)
        return plan

    def stats(self) -> Dict[str, int]:
        """Per-instance counters: ``hits`` (pattern seen before, planning
        skipped), ``misses`` (``group_rows`` ran — or a ``supplier`` filled
        the slot), and ``entries`` (current cache occupancy)."""
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}


# ---------------------------------------------------------------------------
# Operand cache — B-side replicated ELL buffers shared across calls
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _OperandEntry:
    """Cached B operands: the ELL conversion plus its per-shard placements.

    ``source`` pins the origin CSR arrays so their ``id()``s (the cache key)
    cannot be recycled while the entry is alive — jax arrays are immutable,
    so identical ids imply identical contents.

    Each shard holds ``(b_idx, b_val, remap)``: the full replicated ELL with
    ``remap=None``, or a footprint-gathered sub-ELL (only the B rows the
    shard's work items touch) with the global→local row ``remap`` the
    executor threads into that shard's gather programs.  ``footprints``
    keeps the per-shard row selections (``None`` = full replica) so the
    batched lane can slice fresh per-member value planes the same way.
    """

    source: tuple
    b_ell: ELL
    shards: List[tuple]  # per-device (b_idx, b_val, remap-or-None)
    footprints: Optional[List[Optional[np.ndarray]]] = None


def _footprint_fingerprint(footprints) -> Optional[str]:
    """Content digest of a per-shard footprint selection (``None`` = full
    replication everywhere) — the OperandCache key component that keeps
    blocks built for one work partition from serving another."""
    if footprints is None:
        return None
    h = hashlib.blake2b(digest_size=8)
    for fp in footprints:
        if fp is None:
            h.update(b"\xff")
        else:
            fp = np.asarray(fp, np.int64)
            h.update(np.int64(fp.size).tobytes())
            h.update(fp.tobytes())
    return h.hexdigest()


class OperandCache:
    """(B identity, kb_cap, devices, footprint)-keyed cache of placed ELL
    buffers.

    Iterative (MCL with a fixed B, the sampling chain's shared adjacency)
    and batched workloads re-multiply against the *same* B object call after
    call; previously every call re-ran ``csr_to_ell`` and re-placed the
    result onto every shard device.  A hit serves both from the cache —
    zero conversions, zero device transfers.  Lookups fold into the
    module-level ``cache_stats()`` as ``operand_hits``/``operand_misses``,
    and every *build* accumulates the comm-volume counters
    (``operand_bytes_placed``/``operand_rows_footprint``/
    ``operand_rows_total``) — placement cost is paid exactly where it is
    counted.

    ``footprints`` (per-shard B-row selections from the plan's A-support,
    ``None`` entries = full replica) switches a shard from replication to a
    footprint-gathered block: only the selected ELL rows travel to the
    device, plus the global→local ``remap``.  The key carries a content
    fingerprint of the selection, so the same B served under two partitions
    (different meshes, row_chunks) gets distinct block sets.

    Identity keying is only sound for immutable arrays, so CSRs backed by
    mutable buffers (plain NumPy arrays) are *never cached* — they take the
    uncached build path every call, exactly the pre-cache behavior (an
    in-place edit of a NumPy-backed B must be honored, not served stale).
    """

    def __init__(self, max_entries: int = 8):
        self.max_entries = max_entries
        self._entries: "OrderedDict[tuple, _OperandEntry]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        """Drop every cached placement (does not touch the counters)."""
        self._entries.clear()

    @staticmethod
    def _build(b: CSR, kb_cap: int, devices,
               footprints=None) -> _OperandEntry:
        with jax.profiler.TraceAnnotation("spgemm.operands.build"):
            b_ell = csr_to_ell(b, kb_cap)
            n_rows = int(b_ell.indices.shape[0])
            shards = []
            for s, dev in enumerate(devices):
                fp = None if footprints is None else footprints[s]
                if fp is None:
                    shard = (replicate_to(b_ell.indices, dev),
                             replicate_to(b_ell.data, dev), None)
                    rows_placed = n_rows
                else:
                    shard = place_operand_block(b_ell.indices, b_ell.data,
                                                fp, dev)
                    rows_placed = len(fp)
                _OPERAND_STATS["operand_bytes_placed"] += sum(
                    int(x.nbytes) for x in shard if x is not None)
                _OPERAND_STATS["operand_rows_footprint"] += rows_placed
                _OPERAND_STATS["operand_rows_total"] += n_rows
                shards.append(shard)
            return _OperandEntry(
                source=(b.indptr, b.indices, b.data),
                b_ell=b_ell,
                shards=shards,
                footprints=None if footprints is None else list(footprints),
            )

    def b_operands(self, b: CSR, kb_cap: int, devices,
                   footprints=None) -> _OperandEntry:
        """Serve (hit) or build+place (miss) B's per-shard operand entry.

        The key is the identity of B's buffers + ``kb_cap`` + the device
        set + the footprint fingerprint; NumPy-backed CSRs are never
        cached (mutable buffers can be edited in place)."""
        with jax.profiler.TraceAnnotation("spgemm.operands"):
            if not all(isinstance(x, jax.Array)
                       for x in (b.indptr, b.indices, b.data)):
                _OPERAND_STATS["operand_misses"] += 1
                return self._build(b, kb_cap, devices,
                                   footprints)  # mutable: never cache
            key = (
                id(b.indptr), id(b.indices), id(b.data), int(kb_cap),
                tuple(getattr(d, "id", None) for d in devices),
                _footprint_fingerprint(footprints),
            )
            entry = self._entries.get(key)
            if entry is None:
                _OPERAND_STATS["operand_misses"] += 1
                entry = self._build(b, kb_cap, devices, footprints)
                self._entries[key] = entry
                while len(self._entries) > self.max_entries:
                    self._entries.popitem(last=False)
            else:
                _OPERAND_STATS["operand_hits"] += 1
                self._entries.move_to_end(key)
            return entry


_OPERAND_CACHE = OperandCache()


# ---------------------------------------------------------------------------
# Autotune cache — measured per-bin engine assignment for engine="auto"
# ---------------------------------------------------------------------------

def autotune_key(a: "CSR", b: "CSR", plan: GroupPlan) -> tuple:
    """AutotuneCache key: the operands' sparsity-pattern fingerprint (the
    ``PlanCache`` key), the JAX backend (the winning engine is
    backend-dependent — sort on CPU, the fused Pallas lane on TPU), and
    the plan's bin signature (group sizes + table capacities: a different
    binning of the same pattern, e.g. ``ungrouped_plan``, re-measures)."""
    return (pattern_fingerprint(a, b), jax.default_backend(),
            tuple(plan.group_sizes), tuple(plan.table_capacities))


@dataclasses.dataclass
class _AutotuneEntry:
    """Measured per-bin state for one (pattern, backend, bins) key.

    ``pending`` holds each non-empty group's not-yet-measured candidate
    engines (seed heuristic first); ``timings`` the measured µs per
    (group, engine); ``assignment`` the current per-group pick — the
    measured argmin where timings exist, the static seed elsewhere."""

    seed: Tuple[str, ...]
    pending: Dict[int, List[str]]
    timings: Dict[int, Dict[str, float]]
    assignment: Tuple[str, ...]

    @property
    def converged(self) -> bool:
        return not any(self.pending.values())

    def _recompute(self) -> None:
        picks = []
        for g in range(4):
            t = self.timings.get(g)
            picks.append(min(t, key=t.get) if t else self.seed[g])
        self.assignment = tuple(picks)


class AutotuneCache:
    """LRU cache of measured per-bin engine assignments (``engine="auto"``).

    Keyed like ``PlanCache`` (``autotune_key``: pattern fingerprint +
    backend + bin signature).  The first sighting of a key seeds every
    non-empty Table-I group with the static bin-size × backend heuristic
    and queues the remaining registered engines as measurement candidates;
    each subsequent ``engine="auto"`` call measures **one** candidate per
    bin (a timed bin-restricted sub-execution) until the queue drains, so
    iterative workloads (MCL expansion, GNN epochs through
    ``reuse_plan=True``) converge to the measured per-bin optimum within a
    run — after which every call is a pure hit serving the frozen
    assignment with zero re-measurement.  Lookups fold into
    ``cache_stats()`` as ``autotune_hits``/``autotune_misses`` (a miss is
    any round that still measured; a hit is a converged serve).
    """

    def __init__(self, max_entries: int = 64,
                 candidates: Optional[Sequence[str]] = None):
        self.max_entries = max_entries
        self.candidates = tuple(candidates) if candidates else None
        self._entries: "OrderedDict[tuple, _AutotuneEntry]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        """Drop every cached assignment (does not touch the counters)."""
        self._entries.clear()

    def _candidate_order(self, seed_engine: str) -> List[str]:
        cands = self.candidates or available_engines()
        return [seed_engine] + [e for e in sorted(cands) if e != seed_engine]

    def _entry_for(self, key: tuple, plan: GroupPlan) -> _AutotuneEntry:
        entry = self._entries.get(key)
        if entry is None:
            seed = static_bin_engines(plan.table_capacities)
            entry = _AutotuneEntry(
                seed=seed,
                pending={g: self._candidate_order(seed[g])
                         for g in range(4) if plan.group_sizes[g] > 0},
                timings={},
                assignment=seed,
            )
            self._entries[key] = entry
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
        else:
            self._entries.move_to_end(key)
        return entry

    def converged(self, key: tuple) -> bool:
        """True when ``key``'s per-bin assignment has no candidates left
        to measure (every further lookup is a pure hit)."""
        entry = self._entries.get(key)
        return entry is not None and entry.converged

    def assignment_for(self, key: tuple, plan: GroupPlan,
                       measure: Callable[[int, str], float]
                       ) -> Tuple[str, ...]:
        """Serve (hit) or refine (miss + one measurement round) the
        per-bin assignment for ``key``.  ``measure(group, engine)``
        returns the measured wall time in µs; it is only called while
        candidates remain."""
        entry = self._entry_for(key, plan)
        if entry.converged:
            self.hits += 1
            _AUTOTUNE_STATS["autotune_hits"] += 1
            return entry.assignment
        self.misses += 1
        _AUTOTUNE_STATS["autotune_misses"] += 1
        for g, cands in entry.pending.items():
            if cands:
                eng = cands.pop(0)
                entry.timings.setdefault(g, {})[eng] = float(measure(g, eng))
        entry._recompute()
        return entry.assignment

    def record(self, key: tuple, plan: GroupPlan, group: int, engine: str,
               us: float) -> None:
        """Fold one externally-measured timing in (the offline measurement
        loop, ``benchmarks.hillclimb.measure_bin_engines``).  Recording
        every candidate of every non-empty bin converges the entry exactly
        as the incremental in-band rounds would."""
        entry = self._entry_for(key, plan)
        pend = entry.pending.get(group)
        if pend is not None and engine in pend:
            pend.remove(engine)
        entry.timings.setdefault(group, {})[engine] = float(us)
        entry._recompute()

    def stats(self) -> Dict[str, int]:
        """Per-instance counters: ``hits`` / ``misses`` / ``entries``."""
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}

    def summary(self) -> List[Dict]:
        """JSON-friendly view of every entry (bench meta / debugging):
        bin signature, measured timings, and the chosen assignment."""
        return [
            {
                "backend": key[1],
                "group_sizes": list(key[2]),
                "assignment": list(e.assignment),
                "converged": e.converged,
                "timings_us": {str(g): dict(t)
                               for g, t in sorted(e.timings.items())},
            }
            for key, e in self._entries.items()
        ]


_AUTOTUNE_CACHE = AutotuneCache()


def default_autotune_cache() -> AutotuneCache:
    """The module-level cache ``engine="auto"`` uses when no explicit
    ``autotune=`` cache is passed (cleared by ``clear_program_cache``)."""
    return _AUTOTUNE_CACHE


def bin_subplan(plan: GroupPlan, group: int) -> GroupPlan:
    """A plan restricted to one Table-I group (every other bin empty).

    The measurement loop times engines on *one bin at a time*; executing a
    bin-restricted plan runs exactly that bin's chunks through the full
    pipeline (rows outside the bin come back empty), so the measured wall
    time isolates the bin's allocate/accumulate cost under each candidate.
    """
    rows = np.asarray(plan.rows_of_group(group), np.int32)
    sizes = [0, 0, 0, 0]
    sizes[group] = len(rows)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    return GroupPlan(
        map_rows=rows,
        group_id=plan.group_id,
        group_offsets=offsets,
        group_sizes=tuple(sizes),
        group_sizes_padded=tuple(sizes),
        table_capacities=plan.table_capacities,
        max_ip=plan.max_ip,
        total_ip=plan.total_ip,
        row_ip=plan.row_ip,
    )


def measure_group_engine(
    a: "CSR",
    b: "CSR",
    plan: GroupPlan,
    group: int,
    engine: str,
    gather: Gather = "auto",
    row_chunk: int = 4096,
    mesh=None,
    pipeline: Pipeline = "two_wave",
    reps: int = 2,
    warmup: int = 1,
    timer: Callable[[], float] = None,
) -> float:
    """Measured wall time (µs) of one Table-I bin under one engine.

    Runs ``execute_plan`` on the bin-restricted subplan (``bin_subplan``)
    with a *concrete* engine — never ``"auto"``, so measurement cannot
    recurse — ``warmup`` untimed passes first (compilation must not land
    inside the timed region), then the min over ``reps`` timed passes
    (the noise-robust statistic the bench drivers use).  ``timer`` is
    injectable for tests; measurement passes pay their own host syncs, so
    only converged ``engine="auto"`` calls are bound by the two-wave sync
    budget.
    """
    timer = timer or time.perf_counter
    get_engine(engine)  # concrete engines only
    sub = bin_subplan(plan, group)

    def run():
        c, _ = execute_plan(a, b, sub, engine=engine, gather=gather,
                            row_chunk=row_chunk, mesh=mesh,
                            pipeline=pipeline)
        jax.block_until_ready((c.indptr, c.indices, c.data))

    for _ in range(warmup):
        run()
    best = float("inf")
    for _ in range(reps):
        t0 = timer()
        run()
        best = min(best, timer() - t0)
    return best * 1e6


def _autotune_assignment(a, b, plan, gather, row_chunk, mesh, pipeline,
                         cache: Optional[AutotuneCache]) -> Tuple[str, ...]:
    """Resolve ``engine="auto"``'s per-bin assignment through the autotune
    cache (module default unless an explicit cache is threaded)."""
    cache = _AUTOTUNE_CACHE if cache is None else cache

    def measure(g, eng):
        return measure_group_engine(
            a, b, plan, g, eng, gather=gather, row_chunk=row_chunk,
            mesh=mesh, pipeline=pipeline)

    return cache.assignment_for(autotune_key(a, b, plan), plan, measure)


def _jit_named(program: Callable, name: str) -> Callable:
    """``jax.jit(program)`` compiled as the module ``jit_<name>``: the name a
    profiler trace gives the program's device ops, so each executor phase
    (and each Table-I table capacity, ``_t<cap>``) is found by name."""
    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def _build_enumerate(a_cap: int, kb_cap: int, gather: str,
                     remapped: bool = False) -> Callable:
    """Compile the product-enumeration program: A-row gather → B-row gather
    (xla or AIA stream) → intermediate products.  Output stays on device and
    feeds both the allocation and accumulation programs — the gather runs
    once per chunk, not once per phase.

    ``remapped`` programs take the footprint block's global→local row map as
    a trailing operand and translate A's column ids before the B gather
    (``phases.remap_columns``) — the gather backends then index the compact
    sub-ELL exactly as they would the full replica.  Keys are B *column*
    ids, so the products are bit-identical either way."""
    gat = GATHERS[gather]

    def program(a_indptr, a_indices, a_data, rows, b_idx, b_val, remap=None):
        cols_a, vals_a = phases.gather_group_rows(
            a_indptr, a_indices, a_data, rows, a_cap
        )
        if remapped:
            cols_a = phases.remap_columns(cols_a, remap)
        bi, bv = gat(b_idx, b_val, cols_a, kb_cap)
        return phases.combine_products(cols_a, vals_a, bi, bv)

    return _jit_named(program, "spgemm_enumerate")


def _build_allocate(table_cap: int, engine: str) -> Callable:
    eng = get_engine(engine)

    def program(keys):
        return eng.allocate(keys, table_cap)

    return _jit_named(program, f"spgemm_allocate_t{table_cap}")


def _build_accumulate(table_cap: int, out_cap: int, engine: str) -> Callable:
    eng = get_engine(engine)

    def program(keys, vals):
        return eng.accumulate(keys, vals, table_cap, out_cap)

    return _jit_named(program, f"spgemm_accumulate_t{table_cap}")


def _build_enumerate_batched(a_cap: int, kb_cap: int, gather: str,
                             remapped: bool = False) -> Callable:
    """Batched enumerate: structure (keys) computed once, value streams
    carry the leading batch axis.  Shares the allocation program with the
    unbatched path — uniqueCount depends only on keys, so one host sync
    sizes the whole batch.  ``remapped`` as in ``_build_enumerate``."""
    gat = BATCHED_GATHERS[gather]

    def program(a_indptr, a_indices, a_data_b, rows, b_idx, b_val_b,
                remap=None):
        cols_a, vals_a_b = phases.gather_group_rows_batched(
            a_indptr, a_indices, a_data_b, rows, a_cap
        )
        if remapped:
            cols_a = phases.remap_columns(cols_a, remap)
        bi, bv_b = gat(b_idx, b_val_b, cols_a, kb_cap)
        return phases.combine_products_batched(cols_a, vals_a_b, bi, bv_b)

    return _jit_named(program, "spgemm_benumerate")


def _build_accumulate_batched(table_cap: int, out_cap: int,
                              engine: str) -> Callable:
    """vmap the engine's accumulate over the batch's value sets.  Keys are
    shared, so every member produces the same cols/counts: the program
    returns member 0's (cols (R, out_cap), vals (B, R, out_cap), counts
    (R,))."""
    eng = get_engine(engine)

    def program(keys, vals_b):
        cols, vals, counts = jax.vmap(
            lambda v: eng.accumulate(keys, v, table_cap, out_cap))(vals_b)
        return cols[0], vals, counts[0]

    return _jit_named(program, f"spgemm_baccumulate_t{table_cap}")


def _build_fused(a_cap: int, kb_cap: int, gather: str, table_cap: int,
                 out_cap: int, kernel: str, remapped: bool = False
                 ) -> Callable:
    """Compile the fused single-pass program: A-row gather → B-row gather
    (xla or the AIA stream, feeding the table directly) → product
    formation → linear-probe insertion → sorted trim, all one jitted
    program — the enumerate key/value stream never becomes a
    device-resident buffer handed between programs, and no allocate pass
    runs (``out_cap`` comes from the plan's Alg. 1 bounds)."""
    gat = GATHERS[gather]

    def program(a_indptr, a_indices, a_data, rows, b_idx, b_val, remap=None):
        cols_a, vals_a = phases.gather_group_rows(
            a_indptr, a_indices, a_data, rows, a_cap
        )
        if remapped:
            cols_a = phases.remap_columns(cols_a, remap)
        bi, bv = gat(b_idx, b_val, cols_a, kb_cap)
        keys, vals = phases.combine_products(cols_a, vals_a, bi, bv)
        return phases.fused_hash_sorted(keys, vals, table_cap, out_cap,
                                        kernel=kernel)

    return _jit_named(program, f"spgemm_fused_t{table_cap}")


def _build_fused_batched(a_cap: int, kb_cap: int, gather: str,
                         table_cap: int, out_cap: int,
                         remapped: bool = False) -> Callable:
    """Batched fused program: the structural gather and key stream run
    once, the per-member value streams are vmapped through the single-pass
    insert (scan engine — the batch axis rides XLA's vmap, not the Pallas
    grid).  Returns member 0's cols/counts (shared structure) with every
    member's values, like the batched accumulate."""
    gat = BATCHED_GATHERS[gather]

    def program(a_indptr, a_indices, a_data_b, rows, b_idx, b_val_b,
                remap=None):
        cols_a, vals_a_b = phases.gather_group_rows_batched(
            a_indptr, a_indices, a_data_b, rows, a_cap
        )
        if remapped:
            cols_a = phases.remap_columns(cols_a, remap)
        bi, bv_b = gat(b_idx, b_val_b, cols_a, kb_cap)
        keys, vals_b = phases.combine_products_batched(
            cols_a, vals_a_b, bi, bv_b)
        cols, vals, counts = jax.vmap(lambda v: phases.fused_hash_sorted(
            keys, v, table_cap, out_cap, kernel="xla"))(vals_b)
        return cols[0], vals, counts[0]

    return _jit_named(program, f"spgemm_bfused_t{table_cap}")


def _build_segment() -> Callable:
    """Shard-local epilogue half (``phases.reassemble_segment``): segment
    buffers, destination map, and the running offset are donated so chunk
    after chunk updates in place on the shard device."""
    return jax.jit(phases.reassemble_segment, donate_argnums=(0, 1, 2, 3))


def _build_segment_batched() -> Callable:
    return jax.jit(phases.reassemble_segment_batched,
                   donate_argnums=(0, 1, 2, 3))


def _build_merge() -> Callable:
    """Per-shard merge scatter into the (donated) final CSR buffers."""
    return jax.jit(phases.merge_segments, donate_argnums=(0, 1))


def _build_merge_batched() -> Callable:
    return jax.jit(phases.merge_segments_batched, donate_argnums=(0, 1))


def _build_scatter() -> Callable:
    """Jitted device-side reassembly epilogue (one chunk → final buffers).
    Keyed on (padded, out_cap, cap, dtype) like every other program, so
    pow2-quantized capacities keep iterative workloads on cached traces.
    The CSR buffers are *donated*: XLA updates them in place instead of
    copying the whole pow2-capacity output once per chunk (the executor
    rebinds the returned buffers, never touching the donated ones again;
    backends without donation fall back to a copy, still correct)."""
    return jax.jit(phases.reassemble_device, donate_argnums=(0, 1))


def _build_scatter_batched() -> Callable:
    return jax.jit(phases.reassemble_device_batched, donate_argnums=(0, 1))


_BUILDERS = {
    "enumerate": _build_enumerate,
    "allocate": _build_allocate,
    "accumulate": _build_accumulate,
    "benumerate": _build_enumerate_batched,
    "baccumulate": _build_accumulate_batched,
    "fused": _build_fused,
    "bfused": _build_fused_batched,
    "scatter": _build_scatter,
    "bscatter": _build_scatter_batched,
    "segment": _build_segment,
    "bsegment": _build_segment_batched,
    "merge": _build_merge,
    "bmerge": _build_merge_batched,
}


def _get_program(kind: str, key: tuple, *build_args) -> Callable:
    cache_key = (kind,) + key
    prog = _PROGRAM_CACHE.get(cache_key)
    if prog is None:
        _CACHE_STATS["misses"] += 1
        prog = _BUILDERS[kind](*build_args)
        _PROGRAM_CACHE[cache_key] = prog
    else:
        _CACHE_STATS["hits"] += 1
    return prog


@contextlib.contextmanager
def record_lowerings(kinds: Sequence[str] = ("fused", "enumerate")):
    """Record the StableHLO text of each program of ``kinds`` built inside
    the block, lowered from the arguments of its first call.

    Yields ``{kind: [text, ...]}``; lets a caller check what a program
    compiled to (for example that it holds a ``tpu_custom_call`` kernel).
    The program cache is cleared on entry and exit, so every program the
    block runs is built (and recorded) here and no recording wrapper
    outlives it."""
    texts = {k: [] for k in kinds}
    originals = {k: _BUILDERS[k] for k in kinds}

    def wrap(kind, build):
        def builder(*build_args):
            prog = build(*build_args)
            seen = []

            def run(*args):
                if not seen:
                    seen.append(True)
                    texts[kind].append(prog.lower(*args).as_text())
                return prog(*args)

            return run

        return builder

    clear_program_cache()
    _BUILDERS.update({k: wrap(k, b) for k, b in originals.items()})
    try:
        yield texts
    finally:
        _BUILDERS.update(originals)
        clear_program_cache()


# ---------------------------------------------------------------------------
# Plan execution
# ---------------------------------------------------------------------------

def ungrouped_plan(plan: GroupPlan) -> GroupPlan:
    """Collapse to one natural-order group at worst-case capacity
    (the Fig. 7 "without AIA scheduling" software baseline)."""
    n = len(plan.map_rows)
    cap = next_pow2(max(plan.max_ip, 2))
    return GroupPlan(
        map_rows=np.arange(n, dtype=np.int32),
        group_id=np.zeros(n, np.int32),
        group_offsets=np.asarray([0, n, n, n, n], np.int32),
        group_sizes=(n, 0, 0, 0),
        group_sizes_padded=(n, 0, 0, 0),
        table_capacities=(cap, cap, cap, cap),
        max_ip=plan.max_ip,
        total_ip=plan.total_ip,
        row_ip=plan.row_ip,
    )


def _pad_rows(k: int) -> int:
    return int(np.ceil(k / ROW_QUANTUM) * ROW_QUANTUM)


@dataclasses.dataclass(frozen=True)
class WorkItem:
    """One (group, row-chunk) dispatch, pinned to one shard."""

    group: int
    shard: int
    rows: np.ndarray      # (R,) original row ids of this chunk
    a_cap: int            # exact max nnz(A row) over the *group*
    table_cap: int        # Table-I hash-table capacity of the group
    padded: int           # rows the chunk's programs take (padding: -1 ids)
    engine: Optional[str] = None  # per-bin engine (None = caller's engine=)


def partition_plan(
    plan: GroupPlan,
    a_row_nnz: np.ndarray,
    row_chunk: int,
    n_shards: int = 1,
    group_engines: Optional[Tuple[str, ...]] = None,
) -> List[WorkItem]:
    """Split a ``GroupPlan`` into shard-assigned group-chunk work items.

    Chunks are assigned round-robin with a cursor that carries across
    groups, so each shard receives a balanced mix of Table-I bins (a shard
    never ends up holding only the heavy group-3 rows).  With multiple
    shards the chunk size shrinks to ``ceil(group/n_shards)`` (quantized to
    ``ROW_QUANTUM``) so every shard gets work from every group it can.
    Every chunk of a group is padded to the group's first chunk
    (``WorkItem.padded``), so the chunks share their compiled programs.

    ``a_cap`` stays a *group-level* maximum: per-row results then never
    depend on the chunking or the shard count, which is what makes the
    sharded path bit-identical to the single-device one.

    ``group_engines`` (the resolved ``engine="auto"`` assignment, or a
    plan's forced ``plan.group_engines``) stamps each item with its bin's
    engine; ``None`` leaves items on the caller's uniform ``engine=``.
    """
    items: List[WorkItem] = []
    cursor = 0
    for g in range(4):
        rows = plan.rows_of_group(g)
        if len(rows) == 0:
            continue
        a_cap = max(int(a_row_nnz[rows].max(initial=0)), 1)
        table_cap = plan.table_capacities[g]
        chunk = row_chunk
        if n_shards > 1:
            per_shard = _pad_rows(int(np.ceil(len(rows) / n_shards)))
            chunk = max(min(row_chunk, per_shard), ROW_QUANTUM)
        # Every chunk of the group pads to one length, so they share one
        # compiled program per phase.
        padded = _pad_rows(min(chunk, len(rows)))
        for lo in range(0, len(rows), chunk):
            items.append(WorkItem(
                group=g,
                shard=cursor % n_shards,
                rows=np.asarray(rows[lo: lo + chunk]),
                a_cap=a_cap,
                table_cap=table_cap,
                engine=None if group_engines is None else group_engines[g],
                padded=padded,
            ))
            cursor += 1
    return items


_PARTITION_CACHE: Dict[tuple, List[WorkItem]] = {}


def partition_plan_cached(
    plan: GroupPlan,
    a_row_nnz: np.ndarray,
    row_chunk: int,
    n_shards: int = 1,
    group_engines: Optional[Tuple[str, ...]] = None,
) -> List[WorkItem]:
    """Identity-memoized ``partition_plan``: a plan object served twice
    (a ``PlanCache`` hit, an explicit ``plan=`` reuse, or the batched lane)
    reuses its work-item list — iterations and batch members keep the same
    shard assignment under ``mesh=`` instead of re-partitioning.

    Keying on object identity keeps the unamortized path free (no content
    hashing per call), and a ``weakref.finalize`` on the plan evicts the
    entry when the plan dies, so ``id()`` reuse can't alias and the cache
    never outlives the plans it serves.
    """
    key = (id(plan), int(row_chunk), int(n_shards), group_engines)
    items = _PARTITION_CACHE.get(key)
    if items is None:
        items = partition_plan(plan, a_row_nnz, row_chunk, n_shards=n_shards,
                               group_engines=group_engines)
        _PARTITION_CACHE[key] = items
        weakref.finalize(plan, _PARTITION_CACHE.pop, key, None)
    return items


def shard_footprints(items: Sequence[WorkItem], a_indptr: np.ndarray,
                     a_indices: np.ndarray,
                     n_shards: int) -> List[np.ndarray]:
    """Per-shard B-row footprints from the work items' A-support.

    Shard ``s`` will gather exactly the B rows named by the column indices
    of A restricted to the rows of its work items — the union is computed
    on host from the same CSR arrays phase 1 already walked
    (``grouping.support_footprint``).  A shard with no work (or only empty
    rows) gets a single-row footprint ``[0]`` so its block keeps a valid
    ELL shape; nothing ever gathers from it.
    """
    by_shard: List[list] = [[] for _ in range(n_shards)]
    for item in items:
        by_shard[item.shard].append(item.rows)
    out = []
    for rows in by_shard:
        fp = support_footprint(
            a_indptr, a_indices,
            np.concatenate(rows) if rows else np.empty(0, np.int64))
        out.append(fp if fp.size else np.zeros(1, np.int64))
    return out


_FOOTPRINT_CACHE: Dict[tuple, List[np.ndarray]] = {}


def _shard_footprints_cached(plan: GroupPlan, items: Sequence[WorkItem],
                             a: CSR, row_chunk: int, n_shards: int,
                             group_engines) -> List[np.ndarray]:
    """Memoized ``shard_footprints``, keyed like the partition cache: a
    reused plan (same chunking, same shard count) reuses its footprints —
    iterative workloads derive the B placement once, not per call."""
    key = (id(plan), int(row_chunk), int(n_shards), group_engines)
    fps = _FOOTPRINT_CACHE.get(key)
    if fps is None:
        fps = shard_footprints(items, np.asarray(a.indptr),
                               np.asarray(a.indices), n_shards)
        _FOOTPRINT_CACHE[key] = fps
        weakref.finalize(plan, _FOOTPRINT_CACHE.pop, key, None)
    return fps


@dataclasses.dataclass
class _ChunkOut:
    rows: np.ndarray      # (R,) original row ids
    cols: np.ndarray      # (R_pad, out_cap)
    vals: np.ndarray      # (R_pad, out_cap)
    counts: np.ndarray    # (R_pad,)


def _shard_a_operands(a_arrays: Sequence, devices) -> List[tuple]:
    """Replicate A-side arrays onto every shard device.  A is placed per
    call (its values change across iterations); the B-side ELL replicas are
    the expensive, reusable half and ride the ``OperandCache`` (the
    software analogue of the paper's per-stack all-gather: every shard
    serves its two-level indirection from local memory)."""
    return [
        tuple(replicate_to(x, dev) for x in a_arrays) for dev in devices
    ]


def _setup_execution(a: CSR, b: CSR, plan: GroupPlan, engine: str,
                     gather: Gather, row_chunk: int, mesh,
                     group_engines: Optional[Tuple[str, ...]] = None,
                     operands: Operands = "auto"):
    """Shared single-matrix/batched preamble: resolve knobs, derive the
    exact capacities, (memoized) partition the plan over the shards, and
    resolve the per-shard B placement.

    When ``group_engines`` is set (``engine="auto"`` resolved, or a forced
    ``plan.group_engines``), every assigned engine is validated and the
    work items come back stamped per bin; the base ``engine`` may then be
    the string ``"auto"`` and is never dispatched itself.

    The returned ``footprints`` is the resolved ``operands=`` policy:
    ``None`` for full replication on every shard, else one entry per shard
    (row selection, or ``None`` for that shard's full-replica fast path).
    """
    gather = resolve_gather(gather)
    operands = resolve_operands(operands)
    if group_engines is not None:
        for name in group_engines:
            get_engine(name)  # validate the whole assignment early
    else:
        get_engine(engine)  # validate early ("auto" must be resolved first)
    # a_cap/kb_cap stay *exact*: ip_cap = a_cap·kb_cap is the sort engine's
    # dominant dimension and rounding it up is superlinearly expensive.
    # Cache keys still stabilize across iterations because iterative
    # workloads (MCL at fixpoint, GNN layers) keep their sparsity structure.
    kb_cap = int(np.asarray(b.row_nnz()).max(initial=0)) or 1
    # uniqueCount per row is bounded by n_cols(B) regardless of IP.
    ncol_cap = next_pow2(max(b.n_cols, 1))
    a_indptr_np = np.asarray(a.indptr)
    a_row_nnz = a_indptr_np[1:] - a_indptr_np[:-1]
    devices = shard_devices(mesh)
    items = partition_plan_cached(plan, a_row_nnz, row_chunk,
                                  n_shards=len(devices),
                                  group_engines=group_engines)
    footprints = None
    n_shards = len(devices)
    # "auto" only engages under real sharding (one shard's footprint is the
    # whole support — there is no communication to avoid); "footprint"
    # forces blocks everywhere, including single-device, for A/B tests.
    if (operands == "footprint"
            or (operands == "auto" and n_shards > 1)):
        raw = _shard_footprints_cached(plan, items, a, row_chunk, n_shards,
                                       group_engines)
        limit = FOOTPRINT_THRESHOLD * max(b.n_rows, 1)
        footprints = [
            fp if operands == "footprint" or len(fp) < limit else None
            for fp in raw
        ]
        if all(fp is None for fp in footprints):
            footprints = None  # every shard took the replication fast path
    return gather, kb_cap, ncol_cap, devices, items, footprints


def _chunk_rows_padded(item: WorkItem, dev):
    """Pad a chunk's row ids to the item's padded length (-1 = padding
    row) and place them on the item's shard device."""
    chunk, padded = item.rows, item.padded
    rows_j = replicate_to(jnp.asarray(np.concatenate(
        [chunk, -np.ones(padded - len(chunk), np.int32)]
    )), dev)
    return padded, rows_j


def _alloc_counts(keys, padded: int, table_cap: int, engine: str) -> jax.Array:
    """Dispatch the allocation program (Algorithms 2/3) — uniqueCount per
    row, returned *on device* so the caller chooses when to sync.  Keys
    depend only on structure, so the batched lane shares this program (same
    cache key) and one sizing serves every batch member."""
    ip_cap = keys.shape[1]
    alloc = _get_program("allocate", (padded, ip_cap, table_cap, engine),
                         table_cap, engine)
    return alloc(keys)


def _out_cap_from_counts(unique_counts: np.ndarray, table_cap: int,
                         ncol_cap: int) -> int:
    """pow2-quantized chunk output capacity from host-resident uniqueCounts
    (keeps the accumulate signature stable across iterative calls)."""
    max_unique = int(unique_counts.max(initial=0))
    return max(min(next_pow2(max_unique), max(table_cap, 1), ncol_cap), 1)


def _size_out_cap(keys, padded: int, table_cap: int, engine: str,
                  ncol_cap: int) -> int:
    """Legacy per-chunk allocation sizing: one *blocking* host sync per
    group-chunk (the serialization the two-wave pipeline removes)."""
    counts = _alloc_counts(keys, padded, table_cap, engine)
    _SYNC_STATS["host_sync_count"] += 1
    return _out_cap_from_counts(np.asarray(counts), table_cap, ncol_cap)


_INT32_MAX = int(np.iinfo(np.int32).max)


def _int32_nnz_capacity(nnz: int) -> int:
    """Total-nnz capacity of the device epilogue's CSR buffers.

    pow2-quantized so iterative workloads reuse compiled scatter programs;
    the epilogue emits int32 ``indptr``/``indices`` throughout, so a result
    whose nnz does not fit int32 must fail loudly instead of silently
    downcasting (the pre-PR reassembly ``astype(np.int32)`` drift).  If the
    pow2 quantum itself would overflow int32 while the nnz still fits, fall
    back to the exact capacity.
    """
    if nnz > _INT32_MAX:
        raise OverflowError(
            f"SpGEMM output has {nnz} nonzeros, which does not fit the "
            "int32 CSR index space used by the device reassembly epilogue")
    cap = next_pow2(max(nnz, 1))
    return cap if cap <= _INT32_MAX else max(int(nnz), 1)


def _coalesce_and_size(pend: List[tuple], n: int):
    """The two-wave pipeline's single blocking point, shared by the
    single-matrix and batched lanes: drain every pending chunk's allocate
    counts with one coalesced sync, assemble the int32 ``indptr``, and size
    the epilogue's pow2-quantized total-nnz capacity (overflow-guarded).

    ``pend`` entries are ``(item, padded, keys, vals, alloc_counts)``;
    returns ``(unique_counts, indptr, nnz, cap)``.
    """
    with jax.profiler.TraceAnnotation("spgemm.sync"):
        unique_counts = _coalesced_sync([p[4] for p in pend]) if pend else []
        counts_all = np.zeros(n, np.int64)
        for (item, _, _, _, _), uc in zip(pend, unique_counts):
            counts_all[item.rows] = uc[: len(item.rows)]
        indptr64 = np.zeros(n + 1, np.int64)
        np.cumsum(counts_all, out=indptr64[1:])
        nnz = int(indptr64[-1])
        cap = _int32_nnz_capacity(nnz)
        return unique_counts, indptr64.astype(np.int32), nnz, cap


def _chunk_starts(indptr: np.ndarray, rows: np.ndarray, padded: int,
                  merge_dev) -> jax.Array:
    """int32 CSR start offset of each chunk row, padded rows parked at 0
    (their counts are 0, so the epilogue scatter drops them)."""
    starts = np.zeros(padded, np.int32)
    starts[: len(rows)] = indptr[rows]
    return replicate_to(jnp.asarray(starts), merge_dev)


def _scatter_positions(indptr: np.ndarray, rows: np.ndarray,
                       counts: np.ndarray, out_cap: int):
    """Reassembly offsets for one chunk: flat CSR destinations of the
    occupied (row, slot) cells plus the occupancy mask — shared by the
    single-matrix and batched lanes (the batched value scatter just
    broadcasts over its leading axis)."""
    r = len(rows)
    starts = indptr[rows]  # (R,)
    offs = np.arange(out_cap, dtype=np.int64)[None, :]
    pos = starts[:, None] + offs  # (R, out_cap)
    ok = offs < counts[:r, None]
    return pos[ok], ok, r


@dataclasses.dataclass
class _ChunkRun:
    """One chunk's accumulated output, still on its shard device."""

    item: WorkItem
    padded: int
    out_cap: int
    cols: jax.Array    # (R_pad, out_cap)
    vals: jax.Array    # (R_pad, out_cap) or (batch, R_pad, out_cap)
    counts: jax.Array  # (R_pad,)


class _Epilogue:
    """Device-side CSR scatter epilogue — direct or sharded.

    Direct (one shard): each chunk scatters straight into the final
    pow2-capacity buffers on the merge device (the pre-PR-5 path).

    Sharded (>1 shard): each chunk is packed *densely* into its shard's
    local CSR segment on the shard device, together with a destination map
    into the final buffers (``phases.reassemble_segment``); ``finish()``
    then moves one compact ``(segment, values, dest)`` triple per shard to
    the merge device and applies one merge scatter per shard.  The
    reassembly compute runs shard-parallel and the lead device receives
    ``n_shards`` nnz-sized transfers instead of every padded chunk output
    — the ROADMAP's "shard the epilogue" item.  Results are bit-identical
    to the direct path: row destinations are disjoint across shards, so
    every final slot is written by exactly one segment.

    ``seg_caps`` are the per-shard segment capacities (pow2-quantized,
    from measured uniqueCounts or planned Alg. 1 bounds); ``batch`` turns
    on the batched value planes.
    """

    def __init__(self, devices, cap: int, dtype, dt: str,
                 seg_caps: Optional[List[int]] = None,
                 batch: Optional[int] = None):
        self.devices = devices
        self.merge_dev = merge_device(devices)
        self.cap = cap
        self.dt = dt
        self.batch = batch
        self.sharded = len(devices) > 1
        self.idx_buf = replicate_to(jnp.zeros(cap, jnp.int32), self.merge_dev)
        dat_shape = (cap,) if batch is None else (batch, cap)
        self.dat_buf = replicate_to(jnp.zeros(dat_shape, dtype),
                                    self.merge_dev)
        self.segs: Dict[int, list] = {}
        if self.sharded:
            for s, dev in enumerate(devices):
                seg_cap = seg_caps[s]
                if seg_cap == 0:
                    continue  # shard got no work items
                seg_shape = (seg_cap,) if batch is None else (batch, seg_cap)
                self.segs[s] = [
                    replicate_to(jnp.zeros(seg_cap, jnp.int32), dev),
                    replicate_to(jnp.zeros(seg_shape, dtype), dev),
                    # dest sentinel = final capacity → dropped at merge
                    replicate_to(jnp.full(seg_cap, cap, jnp.int32), dev),
                    replicate_to(jnp.zeros((), jnp.int32), dev),
                    seg_cap,
                ]

    def add_chunk(self, run: _ChunkRun, fin_starts: jax.Array) -> None:
        """Consume one chunk's output.  ``fin_starts`` must live on the
        shard device (sharded) or the merge device (direct)."""
        b = () if self.batch is None else (self.batch,)
        if not self.sharded:
            kind = "scatter" if self.batch is None else "bscatter"
            prog = _get_program(
                kind, b + (run.padded, run.out_cap, self.cap, self.dt))
            self.idx_buf, self.dat_buf = prog(
                self.idx_buf, self.dat_buf,
                replicate_to(run.cols, self.merge_dev),
                replicate_to(run.vals, self.merge_dev),
                replicate_to(run.counts, self.merge_dev),
                fin_starts,
            )
            return
        seg = self.segs.get(run.item.shard)
        if seg is None:
            # seg_cap 0: every row this shard owns is bounded/measured at
            # zero output nnz, so there is nothing to pack or merge.
            return
        kind = "segment" if self.batch is None else "bsegment"
        prog = _get_program(
            kind, b + (run.padded, run.out_cap, seg[4], self.dt))
        seg[0], seg[1], seg[2], seg[3] = prog(
            seg[0], seg[1], seg[2], seg[3],
            run.cols, run.vals, run.counts, fin_starts)

    def finish(self) -> Tuple[jax.Array, jax.Array]:
        if self.sharded:
            b = () if self.batch is None else (self.batch,)
            kind = "merge" if self.batch is None else "bmerge"
            for s in sorted(self.segs):
                seg = self.segs[s]
                prog = _get_program(kind, b + (seg[4], self.cap, self.dt))
                self.idx_buf, self.dat_buf = prog(
                    self.idx_buf, self.dat_buf,
                    replicate_to(seg[0], self.merge_dev),
                    replicate_to(seg[1], self.merge_dev),
                    replicate_to(seg[2], self.merge_dev),
                )
        return self.idx_buf, self.dat_buf


def _shard_seg_caps(items: Sequence[WorkItem], n_shards: int,
                    chunk_nnz: Sequence[int]) -> List[int]:
    """Per-shard segment capacities (pow2-quantized) from per-chunk nnz —
    exact counts on the measured path, Alg. 1 bounds on the planned one."""
    totals = [0] * n_shards
    for item, nnz in zip(items, chunk_nnz):
        totals[item.shard] += int(nnz)
    return [next_pow2(t) if t > 0 else 0 for t in totals]


def _device_indptr(runs: Sequence[_ChunkRun], n: int, merge_dev):
    """Sync-free CSR sizing: assemble the int32 indptr *on device* from the
    chunks' device-resident counts (the chunks' rows partition [0, n), so
    one scatter of the concatenated counts covers every row).  Returns
    (indptr (n+1,) int32 device array, nnz () int32 device scalar)."""
    counts_all = replicate_to(jnp.zeros(n, jnp.int32), merge_dev)
    if runs:
        rows_cat = np.concatenate([r.item.rows for r in runs])
        counts_cat = jnp.concatenate([
            replicate_to(r.counts[: len(r.item.rows)], merge_dev)
            for r in runs
        ])
        counts_all = counts_all.at[
            replicate_to(jnp.asarray(rows_cat), merge_dev)].set(counts_cat)
    indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts_all)])
    return indptr, indptr[-1]


def _device_chunk_starts(indptr_dev: jax.Array, rows: np.ndarray,
                         padded: int, dev) -> jax.Array:
    """Per-chunk final CSR start offsets gathered from the device-resident
    indptr (padding rows park at row 0; their counts are 0, so the scatter
    drops them).  ``indptr_dev`` must already live on ``dev``."""
    rows_full = np.zeros(padded, np.int32)
    rows_full[: len(rows)] = rows
    return jnp.take(indptr_dev, replicate_to(jnp.asarray(rows_full), dev))


def execute_plan(
    a: CSR,
    b: CSR,
    plan: GroupPlan,
    engine: str = "sort",
    gather: Gather = "auto",
    row_chunk: int = 4096,
    mesh=None,
    pipeline: Pipeline = "two_wave",
    sizing: Sizing = "auto",
    autotune: Optional[AutotuneCache] = None,
    operands: Operands = "auto",
    operand_cache: Optional[OperandCache] = None,
) -> Tuple[CSR, int]:
    """Run the compiled group pipeline; returns (C, nnz_C).

    ``pipeline="two_wave"`` (default) dispatches *every* chunk's
    enumerate + allocate programs across all shards first, pays **one**
    coalesced blocking host sync to size every chunk's output at once, then
    runs accumulate on the still-device-resident keys and reassembles the
    CSR with the jitted device epilogue (``phases.reassemble_device``) —
    multi-chunk and multi-shard runs no longer serialize on per-chunk
    allocate syncs, and ``indices``/``data`` never round-trip through
    NumPy.  The tradeoff: wave 1 keeps every chunk's intermediate products
    device-resident until wave 2 consumes them (each is freed right after
    its accumulate), so peak memory approaches the *total* intermediate
    products instead of one chunk's worth.  ``pipeline="legacy"`` is the
    pre-pipelined reference path (one blocking sync per chunk, host-side
    reassembly, per-chunk peak memory), kept for A/B benchmarking,
    bit-exactness tests, and memory-bound runs.  ``mesh`` partitions the plan
    across the mesh's devices (round-robin by group); ``mesh=None`` is the
    single-device path — all four combinations produce bit-identical rows.

    ``sizing`` picks how ``out_cap`` and the epilogue capacity are found:
    ``"measured"`` syncs the uniqueCounts (the coalesced sync above);
    ``"planned"`` derives them from the plan's Alg. 1 IP bounds and
    assembles the indptr on device — the call dispatches end-to-end with
    **zero** blocking host syncs (``host_sync_count`` stays flat; ``nnz``
    comes back as a device scalar that only blocks when the caller reads
    it).  ``"auto"`` (default) is planned for fused engines
    (``"fused_hash"``: one single-pass program per chunk, no allocate
    dispatch, no materialized key stream) and measured otherwise.  Under
    more than one shard the epilogue is itself sharded: chunks pack into
    shard-local CSR segments on their own devices and the merge device
    applies one destination-mapped scatter per shard.

    ``engine="auto"`` dispatches *per Table-I bin* (nsparse-style adaptive
    accumulator selection): the assignment comes from
    ``plan.group_engines`` when set (forced mixed dispatch — it also wins
    over a concrete ``engine=``), otherwise from the ``AutotuneCache``
    (``autotune=``, default the module cache): static bin-size × backend
    seeds refined by measured per-bin timings, one candidate measured per
    call until converged.  Sizing then follows the per-bin rule: planned
    iff every non-empty bin's engine is fused, measured the moment any
    bin picks a non-fused engine.

    ``operands`` selects the B-side placement: ``"auto"`` (default) ships
    each shard only the footprint-gathered B block its work items'
    A-support touches (full replica when the footprint covers ≥
    ``FOOTPRINT_THRESHOLD`` of B's rows, and always on a single shard);
    ``"footprint"`` forces the block path, ``"replicate"`` the blind full
    replication.  All three are bit-identical — the remapped gathers read
    the same B rows from shard-local indices — and the comm saving
    surfaces in ``cache_stats()``'s ``operand_bytes_placed`` /
    ``operand_rows_*`` counters.

    ``operand_cache`` scopes the B-side placement cache: ``None`` (default)
    uses the module-level cache; a caller-owned ``OperandCache`` isolates
    placements (and their LRU quota) per scope — the multi-tenant serving
    layer gives each tenant its own instance so one tenant's traffic can
    never evict another's placed buffers.
    """
    if pipeline not in ("two_wave", "legacy"):
        raise ValueError(f"unknown pipeline {pipeline!r}")
    engine = resolve_engine(engine)
    group_engines = plan.group_engines
    if group_engines is None and engine == AUTO_ENGINE:
        group_engines = _autotune_assignment(
            a, b, plan, gather, row_chunk, mesh, pipeline, autotune)
    if pipeline == "legacy":
        if sizing == "planned":
            raise ValueError(
                "sizing='planned' requires pipeline='two_wave' (the legacy "
                "reference path sizes each chunk from a blocking sync)")
        mode = "measured"
    else:
        mode = resolve_sizing(sizing, engine, plan, group_engines)
    budget = _DEVICE_BUDGET["bytes"]
    if budget is not None:
        need = estimated_device_bytes(plan, np.dtype(a.data.dtype).itemsize)
        if need > budget:
            raise DeviceBudgetExceeded(
                f"plan needs ~{need} device bytes for its intermediate "
                f"products (total IP {plan.total_ip}) but the configured "
                f"device budget is {budget}; stream the call instead — "
                "spgemm_streamed with tile_rows small enough that every "
                "tile's estimate fits the budget")
    with jax.profiler.TraceAnnotation("spgemm.setup"):
        gather, kb_cap, ncol_cap, devices, items, footprints = (
            _setup_execution(a, b, plan, engine, gather, row_chunk, mesh,
                             group_engines=group_engines, operands=operands))
    n = a.n_rows
    dtype = np.dtype(a.data.dtype)  # no host round-trip: dtype is metadata
    dt = dtype.str
    ocache = operand_cache if operand_cache is not None else _OPERAND_CACHE
    try:
        faults.fire("gather_fail")
        b_entry = ocache.b_operands(b, ell_width(kb_cap, gather), devices,
                                   footprints=footprints)
    except faults.FaultInjected:
        # Transient placement failure: B-operand gather/placement is
        # idempotent (pure function of B + devices), so one re-issue is the
        # whole recovery (docs/resilience.md).
        b_entry = ocache.b_operands(b, ell_width(kb_cap, gather), devices,
                                   footprints=footprints)
    a_ops = _shard_a_operands((a.indptr, a.indices, a.data), devices)
    shape = (a.n_rows, b.n_cols)
    if pipeline == "legacy":
        return _execute_plan_legacy(
            items, devices, a_ops, b_entry, n, shape, dtype, dt, kb_cap,
            ncol_cap, gather, engine)
    if mode == "planned":
        indptr, idx_buf, dat_buf, nnz, overflow = _run_planned(
            items, devices, a_ops, b_entry.shards, plan, n, dtype, dt,
            kb_cap, ncol_cap, b.n_cols, gather, engine)
        if not _capacity_overflow(overflow):
            return CSR(indptr, idx_buf, dat_buf, shape), nnz
        # Capacity detect-and-retry (docs/resilience.md): an under-sized
        # chunk trimmed its cols/vals buffers below the true uniqueCounts,
        # so the whole planned result is untrustworthy — discard it and
        # fall through to the measured two-wave path below, which re-sizes
        # every chunk from its real counts.  A rare miss costs one retry,
        # never correctness.
        _RESILIENCE_STATS["capacity_retries"] += 1

    # ---- Wave 1: dispatch every chunk's enumerate + allocate, no syncs ----
    with jax.profiler.TraceAnnotation("spgemm.dispatch"):
        pend = []
        for item in items:
            dev = devices[item.shard]
            a_ip, a_ix, a_dt = a_ops[item.shard]
            b_ix, b_vl, b_rm = b_entry.shards[item.shard]
            rmk = b_rm is not None
            padded, rows_j = _chunk_rows_padded(item, dev)
            enum = _get_program(
                "enumerate", (padded, item.a_cap, kb_cap, gather, dt, rmk),
                item.a_cap, kb_cap, gather, rmk)
            keys, vals = enum(a_ip, a_ix, a_dt, rows_j, b_ix, b_vl, b_rm)
            pend.append((item, padded, keys, vals,
                         _alloc_counts(keys, padded, item.table_cap,
                                       item.engine or engine)))

    # ---- The one coalesced host sync: size every out_cap at once ----
    unique_counts, indptr, nnz, cap = _coalesce_and_size(pend, n)

    # ---- Wave 2: accumulate on device-resident keys + device epilogue ----
    with jax.profiler.TraceAnnotation("spgemm.epilogue"):
        epi = _Epilogue(
            devices, cap, dtype, dt,
            seg_caps=_shard_seg_caps(
                [p[0] for p in pend], len(devices),
                [int(uc[: len(p[0].rows)].sum()) for p, uc in
                 zip(pend, unique_counts)]))
        for i, uc in enumerate(unique_counts):
            item, padded, keys, vals, _ = pend[i]
            pend[i] = None  # free this chunk's intermediates once consumed
            eng_name = item.engine or engine
            out_cap = _out_cap_from_counts(uc, item.table_cap, ncol_cap)
            ip_cap = keys.shape[1]
            accum = _get_program(
                "accumulate",
                (padded, ip_cap, item.table_cap, out_cap, eng_name, dt),
                item.table_cap, out_cap, eng_name)
            cols_r, vals_r, counts_r = accum(keys, vals)
            # sharded epilogue: starts/outputs stay on the shard device
            starts_dev = devices[item.shard] if epi.sharded else epi.merge_dev
            epi.add_chunk(
                _ChunkRun(item, padded, out_cap, cols_r, vals_r, counts_r),
                _chunk_starts(indptr, item.rows, padded, starts_dev))
        idx_buf, dat_buf = epi.finish()

    c = CSR(jnp.asarray(indptr), idx_buf, dat_buf, shape)
    return c, nnz


def _run_planned(items, devices, a_ops, b_ops, plan, n, dtype, dt, kb_cap,
                 ncol_cap, ncol, gather, engine, batch=None):
    """The sync-free sizing core shared by the single-matrix and batched
    lanes: every capacity comes from the plan's Alg. 1 IP bounds (host
    arithmetic), the indptr is assembled on device, and the whole run —
    fused single-pass programs (or enumerate + accumulate for non-fused
    engines), device indptr, epilogue — is dispatched without a single
    blocking host sync.  ``nnz`` is returned as a device scalar; it blocks
    only when the caller materializes it.  ``batch`` switches the batched
    program kinds and value planes; ``a_ops``/``b_ops`` are per-shard
    operand tuples either way.  Items stamped with a per-bin engine
    (``engine="auto"``) dispatch their own engine's programs; the sizing
    rule guarantees every engine reaching this sync-free core is fused.
    """
    bounds = [chunk_capacity_bounds(plan, item.rows, ncol) for item in items]
    cap = _int32_nnz_capacity(sum(s for _, s in bounds))
    bkey = () if batch is None else (batch,)
    with jax.profiler.TraceAnnotation("spgemm.dispatch"):
        runs: List[_ChunkRun] = []
        for item, (max_u, _) in zip(items, bounds):
            eng_name = item.engine or engine
            eng = get_engine(eng_name)
            dev = devices[item.shard]
            a_arrs = a_ops[item.shard]
            b_ix, b_vl, b_rm = b_ops[item.shard]
            rmk = b_rm is not None
            padded, rows_j = _chunk_rows_padded(item, dev)
            out_cap = _planned_out_cap(max_u, item.table_cap, ncol_cap)
            if faults.trigger("capacity_undersize"):
                # Chaos hook (docs/resilience.md): shrink this chunk's
                # planned capacity below any real row's uniqueCount so the
                # device-side overflow flag and the measured-capacity retry
                # are exercised.
                out_cap = 1
            if eng.fused:
                if batch is None:
                    kernel = _fused_kernel_mode(dt, item.table_cap)
                    prog = _get_program(
                        "fused",
                        (padded, item.a_cap, kb_cap, item.table_cap, out_cap,
                         gather, dt, kernel, rmk),
                        item.a_cap, kb_cap, gather, item.table_cap, out_cap,
                        kernel, rmk)
                else:
                    prog = _get_program(
                        "bfused",
                        (batch, padded, item.a_cap, kb_cap, item.table_cap,
                         out_cap, gather, dt, rmk),
                        item.a_cap, kb_cap, gather, item.table_cap, out_cap,
                        rmk)
                cols_r, vals_r, counts_r = prog(*a_arrs, rows_j, b_ix, b_vl,
                                                b_rm)
            else:
                enum = _get_program(
                    "enumerate" if batch is None else "benumerate",
                    bkey + (padded, item.a_cap, kb_cap, gather, dt, rmk),
                    item.a_cap, kb_cap, gather, rmk)
                keys, vals = enum(*a_arrs, rows_j, b_ix, b_vl, b_rm)
                accum = _get_program(
                    "accumulate" if batch is None else "baccumulate",
                    bkey + (padded, keys.shape[1], item.table_cap, out_cap,
                            eng_name, dt),
                    item.table_cap, out_cap, eng_name)
                cols_r, vals_r, counts_r = accum(keys, vals)
            runs.append(_ChunkRun(item, padded, out_cap, cols_r, vals_r,
                                  counts_r))

    # ---- Device-side CSR sizing: indptr/nnz never visit the host ----
    with jax.profiler.TraceAnnotation("spgemm.epilogue"):
        merge_dev = merge_device(devices)
        indptr, nnz = _device_indptr(runs, n, merge_dev)

        # Device-side capacity-overflow flag: engine counts are TRUE
        # per-row uniqueCounts (never clipped to out_cap), so ``counts >
        # out_cap`` detects an under-sized chunk whose cols/vals buffers
        # were trimmed.  Computed async here (a handful of scalar
        # reductions, no sync); the caller decides whether to *read* it —
        # see ``_capacity_overflow``.
        overflow = None
        for run in runs:
            f = replicate_to(
                jnp.any(run.counts[: len(run.item.rows)] > run.out_cap),
                merge_dev)
            overflow = f if overflow is None else jnp.logical_or(overflow, f)

        epi = _Epilogue(devices, cap, dtype, dt, batch=batch,
                        seg_caps=_shard_seg_caps(items, len(devices),
                                                 [s for _, s in bounds]))
        indptr_by_dev = {merge_dev: indptr}
        for run in runs:
            dev = devices[run.item.shard] if epi.sharded else merge_dev
            if dev not in indptr_by_dev:
                indptr_by_dev[dev] = replicate_to(indptr, dev)
            epi.add_chunk(run, _device_chunk_starts(
                indptr_by_dev[dev], run.item.rows, run.padded, dev))
        idx_buf, dat_buf = epi.finish()
    return indptr, idx_buf, dat_buf, nnz, overflow


def _capacity_overflow(overflow) -> bool:
    """Read the planned lane's overflow flag — iff it could have tripped.

    On today's sizing lanes a clean planned call can never overflow:
    ``_planned_out_cap`` takes a min over terms that each dominate the
    true uniqueCount (Alg. 1's ``min(IP, ncols)`` bound, the table cap,
    the column count), so the flag is read **only** while the
    ``capacity_undersize`` fault point is armed — the clean planned/fused
    path stays free of blocking host syncs (``host_sync_count == 0``).
    A future ``sizing="estimated"`` lane (OCEAN, arXiv:2604.19004) sizes
    from estimates that *can* undershoot; it will read the flag
    unconditionally and reuse the same measured-capacity retry.
    """
    if overflow is None or not faults.armed("capacity_undersize"):
        return False
    return bool(np.asarray(overflow))


def _execute_plan_legacy(items, devices, a_ops, b_entry, n, shape, dtype, dt,
                         kb_cap, ncol_cap, gather, engine) -> Tuple[CSR, int]:
    """Pre-pipelined reference: one blocking allocate sync per group-chunk
    and NumPy host-side reassembly (``np.asarray`` round-trips)."""
    chunks: List[_ChunkOut] = []
    counts_all = np.zeros(n, np.int64)
    for item in items:
        chunk = item.rows
        dev = devices[item.shard]
        a_ip, a_ix, a_dt = a_ops[item.shard]
        b_ix, b_vl, b_rm = b_entry.shards[item.shard]
        rmk = b_rm is not None
        a_cap, table_cap = item.a_cap, item.table_cap
        padded, rows_j = _chunk_rows_padded(item, dev)
        enum = _get_program(
            "enumerate", (padded, a_cap, kb_cap, gather, dt, rmk),
            a_cap, kb_cap, gather, rmk)
        keys, vals = enum(a_ip, a_ix, a_dt, rows_j, b_ix, b_vl, b_rm)
        ip_cap = keys.shape[1]
        eng_name = item.engine or engine
        out_cap = _size_out_cap(keys, padded, table_cap, eng_name, ncol_cap)
        # ---- Accumulation (Algorithm 5) on the same device arrays ----
        accum = _get_program(
            "accumulate", (padded, ip_cap, table_cap, out_cap, eng_name, dt),
            table_cap, out_cap, eng_name)
        cols_r, vals_r, counts_r = accum(keys, vals)
        out = _ChunkOut(
            rows=np.asarray(chunk),
            cols=np.asarray(cols_r),
            vals=np.asarray(vals_r),
            counts=np.asarray(counts_r),
        )
        counts_all[out.rows] = out.counts[: len(chunk)]
        chunks.append(out)

    # ---- Vectorized CSR reassembly (inverse-permutation scatter) ----
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts_all, out=indptr[1:])
    nnz = int(indptr[-1])
    cap = max(nnz, 1)
    indices = np.zeros(cap, np.int32)
    data = np.zeros(cap, dtype)
    for ck in chunks:
        pos_ok, ok, r = _scatter_positions(indptr, ck.rows, ck.counts,
                                           ck.cols.shape[1])
        indices[pos_ok] = ck.cols[:r][ok]
        data[pos_ok] = ck.vals[:r][ok]

    c = CSR(
        jnp.asarray(indptr.astype(np.int32)),
        jnp.asarray(indices),
        jnp.asarray(data),
        shape,
    )
    return c, nnz


# ---------------------------------------------------------------------------
# Batched execution — one plan, many same-pattern value sets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _BatchChunkOut:
    rows: np.ndarray      # (R,) original row ids
    cols: np.ndarray      # (R_pad, out_cap) shared output structure
    vals: np.ndarray      # (batch, R_pad, out_cap)
    counts: np.ndarray    # (R_pad,)


def _batched_operands(a: CSR, b: CSR, a_data_batch, b_data_batch, width: int,
                      devices, footprints=None, operand_cache=None):
    """Per-shard batched operand placement.  The B-side structural buffers
    (ELL indices + the shared value plane) come from the ``OperandCache``
    (``operand_cache`` scopes it; ``None`` = the module cache); only
    per-call value stacks are placed fresh — sliced to each shard's
    footprint rows when the entry carries footprint-gathered blocks."""
    a_data_batch = np.asarray(a_data_batch)
    if a_data_batch.ndim != 2:
        raise ValueError(
            f"a_data_batch must be (batch, capacity), got {a_data_batch.shape}")
    batch = a_data_batch.shape[0]
    ocache = operand_cache if operand_cache is not None else _OPERAND_CACHE
    b_entry = ocache.b_operands(b, width, devices, footprints=footprints)
    if b_data_batch is None:
        # shared B values: broadcast each shard's cached placement in place
        # (a broadcast of a device-resident array stays on that device)
        b_shards = [
            (b_ix, jnp.broadcast_to(b_vl[None], (batch,) + tuple(b_vl.shape)),
             b_rm)
            for b_ix, b_vl, b_rm in b_entry.shards
        ]
    else:
        b_data_batch = np.asarray(b_data_batch)
        if b_data_batch.shape[0] != batch:
            raise ValueError(
                f"batch mismatch: {batch} A value sets vs "
                f"{b_data_batch.shape[0]} B value sets")
        # structure-only ELL layout, vmapped over value sets
        to_ell_data = jax.jit(jax.vmap(lambda d: csr_to_ell(
            CSR(b.indptr, b.indices, d, b.shape), width).data))
        b_val_b = to_ell_data(jnp.asarray(b_data_batch))
        entry_fps = b_entry.footprints or [None] * len(devices)
        b_shards = []
        for (b_ix, _, b_rm), fp, dev in zip(b_entry.shards, entry_fps,
                                            devices):
            vb = b_val_b if fp is None else jnp.take(
                b_val_b, jnp.asarray(np.asarray(fp, np.int32)), axis=1)
            b_shards.append((b_ix, replicate_to(vb, dev), b_rm))
    a_shards = _shard_a_operands(
        (a.indptr, a.indices, jnp.asarray(a_data_batch)), devices)
    return a_data_batch, batch, a_shards, b_shards


def execute_plan_batched(
    a: CSR,
    b: CSR,
    a_data_batch: Sequence,
    b_data_batch: Optional[Sequence] = None,
    plan: Optional[GroupPlan] = None,
    engine: str = "sort",
    gather: Gather = "auto",
    row_chunk: int = 4096,
    mesh=None,
    pipeline: Pipeline = "two_wave",
    sizing: Sizing = "auto",
    autotune: Optional[AutotuneCache] = None,
    operands: Operands = "auto",
    operand_cache: Optional[OperandCache] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Run the compiled pipeline once for a whole batch of same-pattern
    operands; returns ``(indptr, indices, data_batch, nnz)``.

    ``a``/``b`` carry the shared sparsity structure; ``a_data_batch`` is a
    ``(batch, capacity)`` stack of A value sets, ``b_data_batch`` the same
    for B (``None`` = ``b.data`` is shared by every member).  Because the
    key tensor depends only on structure, the enumerate gathers, the
    allocation sizing (under ``pipeline="two_wave"`` one coalesced host
    sync for *all* chunks of the *entire* batch), the output structure, and
    the reassembly offsets all run once; only the value streams are vmapped
    through the cached accumulate programs.  The output structure is shared
    by construction, so member i's result is
    ``CSR(indptr, indices, data_batch[i], (a.n_rows, b.n_cols))``.

    ``mesh=`` shards exactly like ``execute_plan`` — the (memoized) work
    item partition of the shared plan is computed once and every batch
    member rides the same shard assignment; B's replicated ELL buffers are
    served by the ``OperandCache`` across calls.  Results are bit-identical
    to a per-matrix Python loop for every engine × gather combination.

    ``sizing`` mirrors ``execute_plan``: ``"planned"`` (the fused-engine
    default) sizes every chunk of the whole batch from the plan's Alg. 1
    bounds and assembles the shared indptr on device — zero blocking
    syncs; ``"measured"`` keeps the one coalesced uniqueCount sync.

    ``engine="auto"`` resolves a per-bin assignment exactly as in
    ``execute_plan`` (forced ``plan.group_engines`` wins; otherwise the
    ``AutotuneCache``), and the whole batch rides the one assignment.

    ``operands`` mirrors ``execute_plan``: footprint-gathered B blocks per
    shard under ``"auto"``/``"footprint"`` (per-member value planes are
    sliced to the same footprint rows), full replication under
    ``"replicate"`` — bit-identical either way.  ``operand_cache`` scopes
    the B placement cache exactly as in ``execute_plan``.
    """
    if pipeline not in ("two_wave", "legacy"):
        raise ValueError(f"unknown pipeline {pipeline!r}")
    if plan is None:
        plan = group_rows(a, b)
    engine = resolve_engine(engine)
    group_engines = plan.group_engines
    if group_engines is None and engine == AUTO_ENGINE:
        group_engines = _autotune_assignment(
            a, b, plan, gather, row_chunk, mesh, pipeline, autotune)
    if pipeline == "legacy":
        if sizing == "planned":
            raise ValueError(
                "sizing='planned' requires pipeline='two_wave' (the legacy "
                "reference path sizes each chunk from a blocking sync)")
        mode = "measured"
    else:
        mode = resolve_sizing(sizing, engine, plan, group_engines)
    with jax.profiler.TraceAnnotation("spgemm.setup"):
        gather, kb_cap, ncol_cap, devices, items, footprints = (
            _setup_execution(a, b, plan, engine, gather, row_chunk, mesh,
                             group_engines=group_engines, operands=operands))
    n = a.n_rows
    a_data_batch, batch, a_shards, b_shards = _batched_operands(
        a, b, a_data_batch, b_data_batch, ell_width(kb_cap, gather), devices,
        footprints=footprints, operand_cache=operand_cache)
    dtype = a_data_batch.dtype
    dt = np.dtype(dtype).str
    if pipeline == "legacy":
        return _execute_plan_batched_legacy(
            items, devices, a_shards, b_shards, n, batch, dtype, dt, kb_cap,
            ncol_cap, gather, engine)
    if mode == "planned":
        indptr, idx_buf, dat_buf_b, nnz, overflow = _run_planned(
            items, devices, a_shards, b_shards, plan, n, dtype, dt,
            kb_cap, ncol_cap, b.n_cols, gather, engine, batch=batch)
        if not _capacity_overflow(overflow):
            return indptr, idx_buf, dat_buf_b, nnz
        # Same detect-and-retry as execute_plan: discard the under-sized
        # planned result and fall through to the measured batched waves.
        _RESILIENCE_STATS["capacity_retries"] += 1

    # ---- Wave 1: every chunk's benumerate + allocate, no syncs ----
    with jax.profiler.TraceAnnotation("spgemm.dispatch"):
        pend = []
        for item in items:
            dev = devices[item.shard]
            a_ip, a_ix, a_db = a_shards[item.shard]
            b_ix, b_vb, b_rm = b_shards[item.shard]
            rmk = b_rm is not None
            padded, rows_j = _chunk_rows_padded(item, dev)
            benum = _get_program(
                "benumerate",
                (batch, padded, item.a_cap, kb_cap, gather, dt, rmk),
                item.a_cap, kb_cap, gather, rmk)
            keys, vals_b = benum(a_ip, a_ix, a_db, rows_j, b_ix, b_vb, b_rm)
            pend.append((item, padded, keys, vals_b,
                         _alloc_counts(keys, padded, item.table_cap,
                                       item.engine or engine)))

    # ---- One coalesced host sync sizes all chunks for the whole batch ----
    unique_counts, indptr, nnz, cap = _coalesce_and_size(pend, n)

    # ---- Wave 2: batched accumulate + device epilogue (value scatter
    # broadcast over the batch axis) ----
    with jax.profiler.TraceAnnotation("spgemm.epilogue"):
        epi = _Epilogue(
            devices, cap, dtype, dt, batch=batch,
            seg_caps=_shard_seg_caps(
                [p[0] for p in pend], len(devices),
                [int(uc[: len(p[0].rows)].sum()) for p, uc in
                 zip(pend, unique_counts)]))
        for i, uc in enumerate(unique_counts):
            item, padded, keys, vals_b, _ = pend[i]
            pend[i] = None  # free this chunk's intermediates once consumed
            eng_name = item.engine or engine
            out_cap = _out_cap_from_counts(uc, item.table_cap, ncol_cap)
            ip_cap = keys.shape[1]
            bacc = _get_program(
                "baccumulate",
                (batch, padded, ip_cap, item.table_cap, out_cap, eng_name, dt),
                item.table_cap, out_cap, eng_name)
            cols_rb, vals_rb, counts_rb = bacc(keys, vals_b)
            starts_dev = devices[item.shard] if epi.sharded else epi.merge_dev
            epi.add_chunk(
                _ChunkRun(item, padded, out_cap, cols_rb, vals_rb, counts_rb),
                _chunk_starts(indptr, item.rows, padded, starts_dev))
        idx_buf, dat_buf_b = epi.finish()

    return jnp.asarray(indptr), idx_buf, dat_buf_b, nnz




def _execute_plan_batched_legacy(items, devices, a_shards, b_shards, n,
                                 batch, dtype, dt, kb_cap, ncol_cap, gather,
                                 engine):
    """Pre-pipelined batched reference: per-chunk allocate syncs + NumPy
    shared-structure reassembly."""
    chunks: List[_BatchChunkOut] = []
    counts_all = np.zeros(n, np.int64)
    for item in items:
        chunk = item.rows
        dev = devices[item.shard]
        a_ip, a_ix, a_db = a_shards[item.shard]
        b_ix, b_vb, b_rm = b_shards[item.shard]
        rmk = b_rm is not None
        a_cap, table_cap = item.a_cap, item.table_cap
        padded, rows_j = _chunk_rows_padded(item, dev)
        benum = _get_program(
            "benumerate", (batch, padded, a_cap, kb_cap, gather, dt, rmk),
            a_cap, kb_cap, gather, rmk)
        keys, vals_b = benum(a_ip, a_ix, a_db, rows_j, b_ix, b_vb, b_rm)
        ip_cap = keys.shape[1]
        eng_name = item.engine or engine
        out_cap = _size_out_cap(keys, padded, table_cap, eng_name, ncol_cap)
        # ---- Accumulation vmapped over the batch's value sets ----
        bacc = _get_program(
            "baccumulate",
            (batch, padded, ip_cap, table_cap, out_cap, eng_name, dt),
            table_cap, out_cap, eng_name)
        cols_rb, vals_rb, counts_rb = bacc(keys, vals_b)
        out = _BatchChunkOut(
            rows=np.asarray(chunk),
            cols=np.asarray(cols_rb),
            vals=np.asarray(vals_rb),
            counts=np.asarray(counts_rb),
        )
        counts_all[out.rows] = out.counts[: len(chunk)]
        chunks.append(out)

    # ---- Shared-structure reassembly: offsets computed once, the value
    # scatter broadcast over the batch axis ----
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts_all, out=indptr[1:])
    nnz = int(indptr[-1])
    cap = max(nnz, 1)
    indices = np.zeros(cap, np.int32)
    data_batch = np.zeros((batch, cap), dtype)
    for ck in chunks:
        pos_ok, ok, r = _scatter_positions(indptr, ck.rows, ck.counts,
                                           ck.cols.shape[1])
        indices[pos_ok] = ck.cols[:r][ok]
        data_batch[:, pos_ok] = ck.vals[:, :r][:, ok]

    return (jnp.asarray(indptr.astype(np.int32)), jnp.asarray(indices),
            jnp.asarray(data_batch), nnz)


# ---------------------------------------------------------------------------
# Streamed (out-of-core) lane — row-block tiles through the same pipeline
# ---------------------------------------------------------------------------

def tile_ranges(n_rows: int, tile_rows: int) -> List[Tuple[int, int]]:
    """Row-block tile boundaries: half-open ``[r0, r1)`` ranges of
    ``tile_rows`` rows covering ``[0, n_rows)``.  The last tile is ragged
    when ``tile_rows`` does not divide ``n_rows``; ``tile_rows >= n_rows``
    yields a single (monolithic) tile."""
    return [(r0, min(r0 + tile_rows, n_rows))
            for r0 in range(0, n_rows, tile_rows)]


def execute_plan_streamed(
    a: CSR,
    b: CSR,
    *,
    tile_rows: Optional[int] = None,
    prefetch: Optional[int] = None,
    plan: Optional[PlanCache] = None,
    engine: str = "sort",
    gather: Gather = "auto",
    row_chunk: int = 4096,
    schedule: Schedule = "grouped",
    mesh=None,
    pipeline: Pipeline = "two_wave",
    sizing: Sizing = "auto",
    autotune: Optional[AutotuneCache] = None,
    operands: Operands = "auto",
    operand_cache: Optional[OperandCache] = None,
) -> Tuple[CSR, int, Dict[str, int]]:
    """Out-of-core SpGEMM: stream A through the pipeline in row-block tiles.

    A is treated as host-resident: its CSR arrays are sliced into
    ``tile_rows`` row blocks on the host, each tile's operand arrays are
    staged host→device asynchronously (``launch.sharding.stage_tile``),
    planned through the lane's fingerprint-keyed ``PlanCache`` (tile
    patterns repeat across MCL/GNN iterations, so plans amortize), and run
    through ``execute_plan`` — every knob (engine/gather/mesh/pipeline/
    sizing/operands) means exactly what it means monolithically, applied
    per tile.  ``prefetch`` tiles may be device-resident at once: the
    scheduler stages tile *k+1* (…*k+prefetch−1*) right after dispatching
    tile *k*'s programs and before blocking on tile *k*'s result, so the
    H2D transfers overlap wave-1 compute (``prefetch_overlap_hits`` in
    ``cache_stats()`` counts the tiles that actually overlapped).

    Each completed tile is pulled back as a *compact* CSR segment (exact
    nnz, no padding) and merged on the host by the same destination-mapped
    per-segment scatter the sharded device epilogue uses
    (``phases.merge_segments_host`` — a tile is just another segment).
    Device memory therefore holds only B, ``prefetch`` tiles of A, and one
    tile's pipeline intermediates at a time, and the merged C lives in
    host memory — which is what makes the lane out-of-core: with a
    ``set_device_budget`` cap that the monolithic plan exceeds, the same
    product completes here because the per-tile estimate
    (``estimated_device_bytes`` of the tile plan) shrinks with
    ``tile_rows``.

    Tiles partition rows disjointly and every row is planned into the same
    Table-I bin with the same row content it has monolithically, so the
    merged result is bit-identical to the monolithic lane for every
    engine × gather × pipeline combination (the bit-exactness grid in
    tests/test_streaming.py).

    Returns ``(C, nnz_C, stream_info)`` where ``stream_info`` carries the
    per-call tile counters (``n_tiles``, resolved ``tile_rows`` /
    ``prefetch``, ``max_tile_ip``, ``total_ip``).
    """
    t_rows = resolve_tile_rows(tile_rows)
    depth = resolve_prefetch(prefetch)
    if plan is not None and not isinstance(plan, PlanCache):
        raise TypeError(
            "the streamed lane plans per tile, so plan= must be a "
            f"PlanCache (or None for a call-local cache); got {type(plan)!r}")
    cache = plan if plan is not None else PlanCache()
    n = a.n_rows
    # A's home is host memory in this lane; device-backed inputs are
    # materialized once here (tiny for indptr, and the indices/data pull is
    # the one-time cost of switching a resident matrix to streaming).
    a_indptr = np.asarray(a.indptr)
    a_indices = np.asarray(a.indices)
    a_data = np.asarray(a.data)
    dtype = np.dtype(a_data.dtype)
    stage_dev = merge_device(shard_devices(mesh))
    tiles = tile_ranges(n, t_rows)

    staged: List[tuple] = []
    next_tile = [0]

    def _stage(in_flight: bool) -> None:
        r0, r1 = tiles[next_tile[0]]
        lo, hi = int(a_indptr[r0]), int(a_indptr[r1])
        ipt = np.ascontiguousarray(a_indptr[r0:r1 + 1]) - a_indptr[r0]
        idx_h, dat_h = a_indices[lo:hi], a_data[lo:hi]
        try:
            faults.fire("stage_tile_fail")
            idx_d, dat_d = stage_tile((idx_h, dat_h), stage_dev)
        except faults.FaultInjected:
            # Transient host→device staging failure: staging is idempotent
            # (pure device_put of host slices), so the tile is simply
            # re-staged (docs/resilience.md).
            idx_d, dat_d = stage_tile((idx_h, dat_h), stage_dev)
        _STREAM_STATS["tile_bytes_h2d"] += int(
            ipt.nbytes + idx_h.nbytes + dat_h.nbytes)
        if in_flight:
            _STREAM_STATS["prefetch_overlap_hits"] += 1
        staged.append((r0, r1, ipt, idx_h, dat_h, idx_d, dat_d))
        next_tile[0] += 1

    segments = []
    max_tile_ip = 0
    total_ip = 0
    for _ in range(len(tiles)):
        if not staged:
            _stage(in_flight=False)
        r0, r1, ipt, idx_h, dat_h, idx_d, dat_d = staged.pop(0)
        shape_t = (r1 - r0, a.n_cols)
        # plan on the host-side slices (fingerprinting and Alg. 1 are host
        # arithmetic); compute on the staged device arrays
        tplan = cache.plan_for(CSR(ipt, idx_h, dat_h, shape_t), b)
        _STREAM_STATS["tiles_streamed"] += 1
        max_tile_ip = max(max_tile_ip, int(tplan.total_ip))
        total_ip += int(tplan.total_ip)
        run = None
        if tplan.total_ip > 0:
            run_plan = ungrouped_plan(tplan) if schedule == "natural" else tplan
            run = execute_plan(
                CSR(ipt, idx_d, dat_d, shape_t), b, run_plan, engine=engine,
                gather=gather, row_chunk=row_chunk, mesh=mesh,
                pipeline=pipeline, sizing=sizing, autotune=autotune,
                operands=operands, operand_cache=operand_cache)
        # double buffering: stage the next tile(s) while this tile's
        # dispatched programs are still executing, before blocking below
        while next_tile[0] < len(tiles) and len(staged) < depth - 1:
            _stage(in_flight=run is not None)
        if run is None:
            # a tile with zero intermediate products has only empty C rows
            segments.append((r0, r1, np.zeros(r1 - r0 + 1, np.int32),
                             np.empty(0, np.int32), np.empty(0, dtype)))
        else:
            c_t, _ = run
            t_ipt = np.asarray(c_t.indptr)  # blocks on this tile only
            t_nnz = int(t_ipt[-1])
            segments.append((r0, r1, t_ipt,
                             np.asarray(c_t.indices[:t_nnz]),
                             np.asarray(c_t.data[:t_nnz])))

    # ---- Streamed epilogue: tiles are contiguous disjoint row blocks, so
    # the merged indptr is their offset-shifted concatenation and each
    # segment lands with one destination-mapped scatter ----
    indptr = np.zeros(n + 1, np.int64)
    for r0, r1, t_ipt, _, _ in segments:
        indptr[r0 + 1:r1 + 1] = indptr[r0] + np.asarray(t_ipt[1:], np.int64)
    nnz = int(indptr[-1])
    _int32_nnz_capacity(nnz)  # int32 CSR index-space guard (raises loudly)
    idx_buf = np.empty(max(nnz, 1), np.int32)[:nnz]
    dat_buf = np.empty(max(nnz, 1), dtype)[:nnz]
    for r0, r1, t_ipt, seg_idx, seg_dat in segments:
        dest = int(indptr[r0]) + np.arange(len(seg_idx), dtype=np.int64)
        phases.merge_segments_host(idx_buf, dat_buf, seg_idx, seg_dat, dest)
    c = CSR(jnp.asarray(indptr.astype(np.int32)), jnp.asarray(idx_buf),
            jnp.asarray(dat_buf), (n, b.n_cols))
    stream_info = {
        "n_tiles": len(tiles),
        "tile_rows": t_rows,
        "prefetch": depth,
        "max_tile_ip": max_tile_ip,
        "total_ip": total_ip,
    }
    return c, nnz, stream_info
