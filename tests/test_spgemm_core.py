"""Multi-phase SpGEMM vs dense oracle: both engines, Table-I grouping, API."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import intermediate_products, ip_histogram, spgemm
from repro.core.grouping import assign_groups, build_map
from repro.core.ref import spgemm_dense, intermediate_products_dense
from repro.core.spgemm import spgemm_ell_fixed
from repro.core import hashtable as ht
from repro.sparse import csr_from_dense, csr_to_dense, ell_from_dense, ell_to_dense


def random_sparse(rng, n, m, density=0.2):
    x = rng.standard_normal((n, m)).astype(np.float32)
    mask = rng.random((n, m)) < density
    return np.where(mask, x, 0.0).astype(np.float32)


# ---------------------------------------------------------------------------
# Phase 1: Algorithm 1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,m,da,db", [(8, 6, 7, 0.3, 0.3), (20, 20, 20, 0.1, 0.5),
                                          (5, 30, 4, 0.8, 0.05)])
def test_ip_count_matches_loop_oracle(n, k, m, da, db):
    rng = np.random.default_rng(0)
    a = csr_from_dense(random_sparse(rng, n, k, da))
    b = csr_from_dense(random_sparse(rng, k, m, db))
    ip = np.asarray(intermediate_products(a, b))
    expect = intermediate_products_dense(a, b)
    np.testing.assert_array_equal(ip, expect)


def test_group_assignment_table_i():
    ip = jnp.asarray([0, 31, 32, 511, 512, 8191, 8192, 100000])
    g = np.asarray(assign_groups(ip))
    np.testing.assert_array_equal(g, [0, 0, 1, 1, 2, 2, 3, 3])


def test_map_is_stable_group_sort():
    ip = jnp.asarray([600, 3, 40, 5, 9000, 35])
    m = np.asarray(build_map(ip))
    # group ids: [2,0,1,0,3,1] -> stable sort: rows 1,3 (g0), 2,5 (g1), 0 (g2), 4 (g3)
    np.testing.assert_array_equal(m, [1, 3, 2, 5, 0, 4])


def test_ip_histogram():
    ip = jnp.asarray([0, 10, 100, 1000, 10000])
    h = np.asarray(ip_histogram(ip))
    np.testing.assert_array_equal(h, [2, 1, 1, 1])


# ---------------------------------------------------------------------------
# Algorithm 4 hash table
# ---------------------------------------------------------------------------

def test_hash_insert_semantics():
    tab = ht.make_table(8)
    tab = ht.insert(tab, jnp.int32(5), jnp.float32(1.0))
    tab = ht.insert(tab, jnp.int32(5), jnp.float32(2.0))   # accumulate on hit
    tab = ht.insert(tab, jnp.int32(13), jnp.float32(7.0))  # 13*MULT%8 may collide
    tab = ht.insert(tab, jnp.int32(-1), jnp.float32(99.0))  # padding no-op
    cols, vals, count = ht.extract_sorted(tab)
    assert int(count) == 2
    np.testing.assert_array_equal(np.asarray(cols[:2]), [5, 13])
    np.testing.assert_allclose(np.asarray(vals[:2]), [3.0, 7.0])


def test_hash_collision_storm():
    """All keys map to the same slot class: linear probing must resolve."""
    cap = 16
    # 8 colliding keys? varies
    keys = jnp.asarray(np.arange(0, 8 * cap, cap, dtype=np.int32))
    tab = ht.make_table(cap)
    for k in np.asarray(keys):
        tab = ht.insert(tab, jnp.int32(k), jnp.float32(1.0))
    cols, vals, count = ht.extract_sorted(tab)
    assert int(count) == len(np.unique(np.asarray(keys)))


# ---------------------------------------------------------------------------
# Full pipeline vs dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["sort", "hash"])
@pytest.mark.parametrize("n,k,m,da,db", [
    (8, 6, 7, 0.3, 0.3),
    (16, 16, 16, 0.15, 0.15),
    (12, 5, 20, 0.5, 0.4),
    (6, 6, 6, 0.0, 0.3),   # empty A
    (6, 6, 6, 1.0, 1.0),   # dense
])
def test_spgemm_matches_dense(method, n, k, m, da, db):
    rng = np.random.default_rng(42)
    a = csr_from_dense(random_sparse(rng, n, k, da))
    b = csr_from_dense(random_sparse(rng, k, m, db))
    res = spgemm(a, b, method=method)
    got = np.asarray(csr_to_dense(res.c))
    expect = np.asarray(spgemm_dense(a, b))
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)


def test_spgemm_self_product():
    """Matrix self-product A@A — the paper's Table II workload shape."""
    rng = np.random.default_rng(1)
    x = random_sparse(rng, 30, 30, 0.1)
    a = csr_from_dense(x)
    res = spgemm(a, a, method="sort")
    np.testing.assert_allclose(
        np.asarray(csr_to_dense(res.c)), x @ x, rtol=1e-4, atol=1e-5
    )
    # info counters are consistent
    assert res.info["intermediate_products"] >= res.info["nnz_c"]
    assert res.info["flops"] == 2 * res.info["intermediate_products"]


def test_spgemm_engines_agree():
    rng = np.random.default_rng(2)
    a = csr_from_dense(random_sparse(rng, 25, 18, 0.2))
    b = csr_from_dense(random_sparse(rng, 18, 22, 0.25))
    r1 = spgemm(a, b, method="sort")
    r2 = spgemm(a, b, method="hash")
    np.testing.assert_allclose(
        np.asarray(csr_to_dense(r1.c)), np.asarray(csr_to_dense(r2.c)),
        rtol=1e-4, atol=1e-5,
    )


def test_spgemm_deterministic():
    rng = np.random.default_rng(3)
    a = csr_from_dense(random_sparse(rng, 20, 20, 0.3))
    r1 = spgemm(a, a, method="hash")
    r2 = spgemm(a, a, method="hash")
    np.testing.assert_array_equal(np.asarray(r1.c.data), np.asarray(r2.c.data))
    np.testing.assert_array_equal(np.asarray(r1.c.indices), np.asarray(r2.c.indices))


def test_spgemm_ell_fixed_jit_and_scan():
    """The in-graph variant: correct under jit and inside lax.scan (MCL shape)."""
    rng = np.random.default_rng(4)
    x = random_sparse(rng, 12, 12, 0.25)
    e = ell_from_dense(x, k_cap=8)

    @jax.jit
    def sq(e):
        return spgemm_ell_fixed(e, e, out_cap=12)

    c = sq(e)
    np.testing.assert_allclose(np.asarray(ell_to_dense(c)), x @ x, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 10), k=st.integers(1, 10), m=st.integers(1, 10),
    seed=st.integers(0, 2**16),
)
def test_property_spgemm_equals_dense(n, k, m, seed):
    rng = np.random.default_rng(seed)
    a = csr_from_dense(random_sparse(rng, n, k, 0.3))
    b = csr_from_dense(random_sparse(rng, k, m, 0.3))
    res = spgemm(a, b, method="sort")
    np.testing.assert_allclose(
        np.asarray(csr_to_dense(res.c)),
        np.asarray(csr_to_dense(a)) @ np.asarray(csr_to_dense(b)),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# Compile-friendly shapes: padded product streams, piecewise scatters,
# equal row chunks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ip_cap,width", [(1, 1), (12, 16), (189, 256),
                                          (1024, 1024), (1025, 2048),
                                          (2955, 3072), (349281, 350208)])
def test_stream_width(ip_cap, width):
    from repro.core.phases import stream_width

    assert stream_width(ip_cap) == width


def test_padded_stream_changes_no_product():
    """The padding slots are -1 keys with 0 values, which every engine
    skips: the accumulated rows equal those of the unpadded stream."""
    from repro.core import phases

    rng = np.random.default_rng(3)
    cols_a = jnp.asarray(rng.integers(-1, 6, (5, 3)), jnp.int32)
    vals_a = jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)
    bi = jnp.asarray(rng.integers(-1, 9, (5, 3, 7)), jnp.int32)
    bv = jnp.asarray(rng.standard_normal((5, 3, 7)), jnp.float32)
    keys, vals = phases.combine_products(cols_a, vals_a, bi, bv)
    assert keys.shape == (5, 32)
    assert bool(jnp.all(keys[:, 21:] == -1)) and bool(jnp.all(vals[:, 21:] == 0))
    for got, want in zip(phases.sort_unique(keys, vals, 9),
                         phases.sort_unique(keys[:, :21], vals[:, :21], 9)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lead", [(), (3,)])
def test_scatter_drop_in_pieces_matches_one_scatter(monkeypatch, lead):
    from repro.core import phases

    rng = np.random.default_rng(0)
    cap = 5000
    buf = jnp.asarray(rng.standard_normal(lead + (cap,)), jnp.float32)
    pos = jnp.asarray(rng.permutation(cap + 300)[:600].reshape(40, 15),
                      jnp.int32)  # some positions past the end: dropped
    upd = jnp.asarray(rng.standard_normal(lead + (40, 15)), jnp.float32)
    want = buf.at[..., pos].set(upd, mode="drop")
    monkeypatch.setattr(phases, "SCATTER_PIECE", 1024)
    got = phases.scatter_drop(buf, pos, upd)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_partition_plan_chunks_of_a_group_pad_alike():
    """A group split into several chunks pads every chunk to one length,
    so the chunks share their compiled programs."""
    from repro.core import executor
    from repro.core.grouping import group_rows

    rng = np.random.default_rng(2)
    a = csr_from_dense(np.where(rng.random((300, 40)) < 0.05, 1.0, 0.0)
                       .astype(np.float32))
    b = csr_from_dense(np.where(rng.random((40, 30)) < 0.05, 1.0, 0.0)
                       .astype(np.float32))
    plan = group_rows(a, b)
    items = executor.partition_plan(plan, np.diff(np.asarray(a.indptr)), 64)
    by_group = {}
    for it in items:
        assert len(it.rows) <= 64
        assert it.padded >= len(it.rows)
        by_group.setdefault(it.group, set()).add(it.padded)
    assert any(len([i for i in items if i.group == g]) > 1 for g in by_group)
    assert all(len(lengths) == 1 for lengths in by_group.values())
