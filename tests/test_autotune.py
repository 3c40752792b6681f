"""engine="auto" per-bin adaptive dispatch: validation, cache, bit-exactness.

Four layers of coverage:

* ``resolve_engine`` — the one validation path every façade/app entry point
  now routes through (typo → immediate error naming valid choices).
* ``AutotuneCache`` — hit on same-support/different-values operands,
  invalidation on index mutation, LRU bound: the same bars as the
  ``PlanCache`` tests in test_executor.py, keyed the same way.
* Convergence — an unconverged key measures one candidate per bin per
  call; once the queue drains every call is a pure hit with ZERO
  re-measurement (the contract the medium bench tier gates in CI).
* Bit-exactness — ``engine="auto"`` (measured assignment AND forced-mixed
  per-bin assignments via ``plan.group_engines``) matches the dense oracle
  for every gather × schedule × pipeline combination, single and batched.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import executor
from repro.core.grouping import group_rows
from repro.core.ref import spgemm_dense
from repro.core.spgemm import spgemm, spgemm_batched, spgemm_ell_fixed
from repro.sparse.formats import (
    csr_from_dense, csr_to_dense, ell_from_dense,
)

GATHERS = ("xla", "aia")
SCHEDULES = ("grouped", "natural")
PIPELINES = ("two_wave", "legacy")


def int_sparse(rng, n, m, density=0.3):
    x = rng.integers(-4, 5, (n, m)).astype(np.float32)
    mask = rng.random((n, m)) < density
    return np.where(mask, x, 0.0).astype(np.float32)


def _dense(c):
    return np.asarray(csr_to_dense(c))


def _operands(seed=7, n=18, k=14, m=16):
    rng = np.random.default_rng(seed)
    a = csr_from_dense(int_sparse(rng, n, k, 0.25))
    b = csr_from_dense(int_sparse(rng, k, m, 0.35))
    return a, b


def _multibin_operands():
    """Operands whose plan populates several Table-I groups — the fixture
    every forced-mixed test needs.  A mixes single-nnz rows (IP < 32 →
    group 0), ~0.25-density rows (IP ≈ 150 → group 1) and full rows
    (IP ≈ nnz(B) ≈ 620 → group 2)."""
    rng = np.random.default_rng(2)
    xa = np.zeros((64, 48), np.float32)
    for i in range(24):
        xa[i, rng.integers(0, 48)] = float(rng.integers(1, 5))
    xa[24:48] = int_sparse(rng, 24, 48, 0.25)
    xa[48:] = rng.integers(1, 5, (16, 48)).astype(np.float32)
    a = csr_from_dense(xa)
    b = csr_from_dense(int_sparse(rng, 48, 52, 0.25))
    plan = group_rows(a, b)
    assert sum(s > 0 for s in plan.group_sizes) >= 3, plan.group_sizes
    return a, b, plan


def _stub_measure(timings=None, calls=None):
    """measure(group, engine) stub: record calls, serve canned µs."""
    def measure(group, engine):
        if calls is not None:
            calls.append((group, engine))
        if timings is not None:
            return timings[(group, engine)]
        return 100.0
    return measure


# ---------------------------------------------------------------------------
# resolve_engine: the single validation chokepoint
# ---------------------------------------------------------------------------

def test_resolve_engine_accepts_registered_and_auto():
    for name in executor.available_engines():
        assert executor.resolve_engine(name) == name
    assert executor.resolve_engine("auto") == "auto"
    assert executor.resolve_engine(None) == "sort"          # default
    assert executor.resolve_engine(None, method="hash") == "hash"
    assert executor.resolve_engine("hash", method="hash") == "hash"


def test_resolve_engine_typo_names_valid_choices():
    with pytest.raises(ValueError) as e:
        executor.resolve_engine("osrt")
    msg = str(e.value)
    assert "unknown engine 'osrt'" in msg
    for name in executor.available_engines():
        assert name in msg
    assert "auto" in msg


def test_resolve_engine_rejects_conflicting_alias():
    with pytest.raises(ValueError, match="conflicting method"):
        executor.resolve_engine("sort", method="hash")


def test_facades_validate_engine_up_front():
    a, b = _operands()
    with pytest.raises(ValueError, match="unknown engine"):
        spgemm(a, b, engine="osrt")
    with pytest.raises(ValueError, match="unknown engine"):
        spgemm_batched([a], b, engine="osrt")
    with pytest.raises(ValueError, match="conflicting method"):
        spgemm(a, b, engine="sort", method="hash")


def test_ell_fixed_rejects_auto():
    rng = np.random.default_rng(4)
    e = ell_from_dense(int_sparse(rng, 12, 12, 0.25), k_cap=8)
    with pytest.raises(ValueError, match="Table-I bins"):
        spgemm_ell_fixed(e, e, out_cap=12, engine="auto")
    with pytest.raises(ValueError, match="unknown engine"):
        spgemm_ell_fixed(e, e, out_cap=12, engine="osrt")


def test_static_bin_engines_backend_seed():
    caps = (64, 1024, 8192, 16384)  # Table I, group 3 sized at plan time
    assert executor.static_bin_engines(caps, "tpu") == ("fused_hash",) * 4
    assert executor.static_bin_engines(caps, "cpu") == ("sort",) * 4
    assert executor.static_bin_engines(caps, "gpu") == ("sort",) * 4
    seed = executor.static_bin_engines(caps)  # live backend
    assert len(seed) == 4 and all(e in executor.ENGINES for e in seed)


def test_static_bin_engines_routes_oversized_tables_to_sort():
    """A bin whose table exceeds the hash kernel's SMEM budget starts on
    sort on TPU; the fused kernel mode sends such a table to the scan
    engine instead of the Pallas kernel."""
    from repro.kernels.hash_accum import MAX_TABLE_CAP

    caps = (64, 1024, 8192, 2 * MAX_TABLE_CAP)
    assert executor.static_bin_engines(caps, "tpu") == (
        "fused_hash", "fused_hash", "fused_hash", "sort")
    assert executor.static_bin_engines(caps, "cpu") == ("sort",) * 4
    f32 = np.dtype(np.float32).str
    assert executor._fused_kernel_mode(f32, 2 * MAX_TABLE_CAP) == "xla"


# ---------------------------------------------------------------------------
# Sizing rule: planned only when every non-empty bin resolved fused
# ---------------------------------------------------------------------------

def test_resolve_sizing_auto_with_per_bin_assignment():
    a, b = _operands()
    plan = group_rows(a, b)
    fused = ("fused_hash",) * 4
    mixed = tuple("sort" if plan.group_sizes[g] > 0 else "fused_hash"
                  for g in range(4))
    assert executor.resolve_sizing("auto", "auto", plan, fused) == "planned"
    assert executor.resolve_sizing("auto", "auto", plan, mixed) == "measured"
    # an all-fused assignment on the empty bins only: the non-empty bins
    # drive the rule, so a single non-fused populated bin forces measured
    one_sort = list(fused)
    populated = next(g for g in range(4) if plan.group_sizes[g] > 0)
    one_sort[populated] = "sort"
    assert executor.resolve_sizing(
        "auto", "auto", plan, tuple(one_sort)) == "measured"


def test_engines_in_use_restricts_to_populated_bins():
    a, b = _operands()
    plan = group_rows(a, b)
    ge = tuple("hash" if plan.group_sizes[g] > 0 else "sort"
               for g in range(4))
    assert set(executor._engines_in_use("auto", plan, ge)) == {"hash"}
    assert executor._engines_in_use("sort", plan, None) == ("sort",)


def test_forced_all_fused_auto_pays_zero_host_syncs():
    """plan.group_engines all-fused under engine="auto" takes the planned
    sizing lane: the whole call dispatches with zero blocking syncs."""
    a, b = _operands()
    forced = dataclasses.replace(group_rows(a, b),
                                 group_engines=("fused_hash",) * 4)
    spgemm(a, b, engine="auto", plan=forced)  # warm
    s0 = executor.cache_stats()["host_sync_count"]
    res = spgemm(a, b, engine="auto", plan=forced)
    assert executor.cache_stats()["host_sync_count"] == s0
    np.testing.assert_array_equal(_dense(res.c), np.asarray(spgemm_dense(a, b)))


# ---------------------------------------------------------------------------
# AutotuneCache: PlanCache's bars, same key discipline
# ---------------------------------------------------------------------------

def test_autotune_cache_hits_on_same_support_different_values():
    rng = np.random.default_rng(21)
    pattern = rng.random((24, 24)) < 0.25
    m1, m2 = [csr_from_dense(np.where(
        pattern, rng.integers(1, 5, (24, 24)), 0.0).astype(np.float32))
        for _ in range(2)]
    plan = group_rows(m1, m1)
    cache = executor.AutotuneCache(candidates=("sort",))
    calls = []
    cache.assignment_for(executor.autotune_key(m1, m1, plan), plan,
                         _stub_measure(calls=calls))
    assert cache.stats()["misses"] == 1 and calls  # measured the seed
    n_calls = len(calls)
    # same support, different values → same key → converged pure hit
    asg = cache.assignment_for(executor.autotune_key(m2, m2, plan), plan,
                               _stub_measure(calls=calls))
    assert cache.stats() == {"hits": 1, "misses": 1, "entries": 1}
    assert len(calls) == n_calls, "converged lookup re-measured"
    assert asg == ("sort",) * 4


def test_autotune_cache_invalidated_by_index_mutation():
    """Same nnz, one column index changed → different fingerprint → the
    mutated pattern measures from scratch (its binning may differ)."""
    import jax.numpy as jnp
    from repro.sparse.formats import CSR

    rng = np.random.default_rng(22)
    a = csr_from_dense(int_sparse(rng, 16, 16, 0.3))
    b = csr_from_dense(int_sparse(rng, 16, 12, 0.3))
    cache = executor.AutotuneCache(candidates=("sort",))
    plan = group_rows(a, b)
    cache.assignment_for(executor.autotune_key(a, b, plan), plan,
                         _stub_measure())
    ind = np.asarray(a.indices).copy()
    row0 = np.asarray(a.indptr)[:2]
    assert row0[1] > row0[0]
    ind[row0[0]] = (ind[row0[0]] + 1) % a.n_cols
    mutated = CSR(a.indptr, jnp.asarray(ind), a.data, a.shape)
    mplan = group_rows(mutated, b)
    cache.assignment_for(executor.autotune_key(mutated, b, mplan), mplan,
                         _stub_measure())
    assert cache.stats() == {"hits": 0, "misses": 2, "entries": 2}


def test_autotune_cache_keys_on_bin_signature():
    """Same pattern, different binning (ungrouped single-bin plan) → a
    separate entry: per-bin timings don't transfer across bin layouts."""
    a, b = _operands()
    grouped = group_rows(a, b)
    natural = executor.ungrouped_plan(grouped)
    cache = executor.AutotuneCache(candidates=("sort",))
    cache.assignment_for(executor.autotune_key(a, b, grouped), grouped,
                         _stub_measure())
    cache.assignment_for(executor.autotune_key(a, b, natural), natural,
                         _stub_measure())
    assert cache.stats()["entries"] == 2 and cache.misses == 2


def test_autotune_cache_lru_bound():
    rng = np.random.default_rng(24)
    cache = executor.AutotuneCache(max_entries=2, candidates=("sort",))
    mats = [csr_from_dense(int_sparse(rng, 10, 10, 0.4)) for _ in range(3)]
    keys = []
    for m in mats:
        plan = group_rows(m, m)
        key = executor.autotune_key(m, m, plan)
        keys.append((key, plan))
        cache.assignment_for(key, plan, _stub_measure())
    assert len(cache) == 2
    assert not cache.converged(keys[0][0])  # evicted
    cache.assignment_for(*keys[0], _stub_measure())  # re-measures: a miss
    assert cache.misses == 4 and cache.hits == 0


def test_autotune_argmin_beats_seed():
    """Measured timings override the static seed: the per-bin argmin wins
    even when the seed engine was measured first."""
    a, b, plan = _multibin_operands()
    cache = executor.AutotuneCache()
    seed = executor.static_bin_engines(plan.table_capacities)
    best = next(e for e in executor.available_engines() if e != seed[0])
    timings = {(g, e): (50.0 if e == best else 100.0)
               for g in range(4) for e in executor.available_engines()}
    key = executor.autotune_key(a, b, plan)
    for _ in range(len(executor.available_engines())):
        asg = cache.assignment_for(key, plan, _stub_measure(timings))
    assert cache.converged(key)
    for g in range(4):
        assert asg[g] == (best if plan.group_sizes[g] > 0 else seed[g])


def test_autotune_stats_fold_into_cache_stats():
    a, b = _operands()
    plan = group_rows(a, b)
    executor.clear_program_cache()
    assert executor.cache_stats()["autotune_hits"] == 0
    assert executor.cache_stats()["autotune_misses"] == 0
    cache = executor.AutotuneCache(candidates=("sort",))
    key = executor.autotune_key(a, b, plan)
    cache.assignment_for(key, plan, _stub_measure())
    cache.assignment_for(key, plan, _stub_measure())
    stats = executor.cache_stats()
    assert stats["autotune_misses"] == 1 and stats["autotune_hits"] == 1


# ---------------------------------------------------------------------------
# Convergence through the public engine="auto" path
# ---------------------------------------------------------------------------

def test_auto_converges_then_serves_pure_hits():
    """The iterative-workload contract: after one in-band measurement round
    per candidate, every further call is a hit with zero re-measurement,
    and the converged result stays bit-exact."""
    a, b = _operands()
    oracle = np.asarray(spgemm_dense(a, b))
    tuner = executor.AutotuneCache()
    n_rounds = len(executor.available_engines())
    for _ in range(n_rounds):
        res = spgemm(a, b, engine="auto", autotune=tuner)
        np.testing.assert_array_equal(_dense(res.c), oracle)
    assert tuner.misses == n_rounds
    key = executor.autotune_key(a, b, res.plan)
    assert tuner.converged(key)
    hits0, misses0 = tuner.hits, tuner.misses
    res = spgemm(a, b, engine="auto", autotune=tuner)
    assert (tuner.hits, tuner.misses) == (hits0 + 1, misses0)
    np.testing.assert_array_equal(_dense(res.c), oracle)
    # every populated bin measured every candidate
    [entry] = tuner._entries.values()
    plan = res.plan
    for g in range(4):
        if plan.group_sizes[g] > 0:
            assert set(entry.timings[g]) == set(executor.available_engines())


def test_auto_summary_is_json_friendly():
    import json

    a, b = _operands()
    tuner = executor.AutotuneCache(candidates=("sort",))
    spgemm(a, b, engine="auto", autotune=tuner)
    [summary] = tuner.summary()
    json.dumps(summary)  # no numpy scalars / tuples leaking through
    assert summary["group_sizes"] and len(summary["assignment"]) == 4


# ---------------------------------------------------------------------------
# Bit-exactness grid: auto == dense oracle on every axis combination
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gather", GATHERS)
@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("pipeline", PIPELINES)
def test_auto_grid_matches_oracle(gather, schedule, pipeline):
    a, b = _operands()
    res = spgemm(a, b, engine="auto", gather=gather, schedule=schedule,
                 pipeline=pipeline, autotune=executor.AutotuneCache())
    np.testing.assert_array_equal(_dense(res.c), np.asarray(spgemm_dense(a, b)))


@pytest.mark.parametrize("pipeline", PIPELINES)
def test_forced_mixed_assignment_matches_oracle(pipeline):
    """plan.group_engines with *different* engines on different populated
    bins — the dispatch shape the autotuner will pick on real hardware —
    stays bit-exact on both sync structures."""
    a, b, plan = _multibin_operands()
    populated = [g for g in range(4) if plan.group_sizes[g] > 0]
    names = executor.available_engines()
    ge = ["sort"] * 4
    for i, g in enumerate(populated):
        ge[g] = names[i % len(names)]
    assert len({ge[g] for g in populated}) >= 2  # genuinely mixed
    forced = dataclasses.replace(plan, group_engines=tuple(ge))
    res = spgemm(a, b, engine="auto", plan=forced, pipeline=pipeline)
    np.testing.assert_array_equal(_dense(res.c), np.asarray(spgemm_dense(a, b)))
    # forced assignment wins over the call-level engine too
    res2 = spgemm(a, b, engine="sort", plan=forced, pipeline=pipeline)
    np.testing.assert_array_equal(_dense(res2.c), _dense(res.c))


def test_setup_execution_rejects_unknown_group_engine():
    a, b, plan = _multibin_operands()
    forced = dataclasses.replace(plan, group_engines=("sort", "osrt",
                                                      "sort", "sort"))
    with pytest.raises(ValueError, match="unknown engine"):
        spgemm(a, b, plan=forced)


def test_batched_auto_matches_per_member_oracle():
    rng = np.random.default_rng(31)
    pat_a = rng.random((18, 14)) < 0.3
    a_mats = [csr_from_dense(np.where(
        pat_a, rng.integers(1, 5, (18, 14)), 0.0).astype(np.float32))
        for _ in range(3)]
    b = csr_from_dense(int_sparse(rng, 14, 16, 0.35))
    tuner = executor.AutotuneCache()
    res = spgemm_batched(a_mats, b, engine="auto", autotune=tuner)
    for i in range(3):
        np.testing.assert_array_equal(
            _dense(res.cs[i]), np.asarray(spgemm_dense(a_mats[i], b)))
    # the batch shares one pattern → exactly one autotune entry
    assert len(tuner) == 1


# ---------------------------------------------------------------------------
# Measurement plumbing: bin_subplan + measure_group_engine
# ---------------------------------------------------------------------------

def test_bin_subplan_isolates_one_group():
    a, b, plan = _multibin_operands()
    populated = [g for g in range(4) if plan.group_sizes[g] > 0]
    for g in populated:
        sub = executor.bin_subplan(plan, g)
        assert sub.group_sizes[g] == plan.group_sizes[g]
        assert sum(sub.group_sizes) == plan.group_sizes[g]
        np.testing.assert_array_equal(
            np.sort(sub.map_rows), np.sort(plan.rows_of_group(g)))
        # the subplan must execute through the normal pipeline
        c, _ = executor.execute_plan(a, b, sub, engine="sort")
        assert c.indptr.shape[0] == a.n_rows + 1


def test_measure_group_engine_rejects_auto_and_times_with_stub_timer():
    a, b, plan = _multibin_operands()
    g = next(i for i in range(4) if plan.group_sizes[i] > 0)
    with pytest.raises(ValueError, match="unknown engine"):
        executor.measure_group_engine(a, b, plan, g, "auto")
    ticks = iter(range(100))
    us = executor.measure_group_engine(a, b, plan, g, "sort",
                                       timer=lambda: float(next(ticks)))
    assert us > 0  # monotone stub timer → positive measured µs
