"""Pallas Algorithm-4 kernel vs the JAX hash engine (Algorithm 4 oracle)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import phases
from repro.kernels.hash_accum import hash_accumulate, hash_accumulate_sorted


def _as_sorted_pairs(cols, vals, count):
    """Order-independent comparison: (col, val) pairs of the valid prefix."""
    out = []
    for r in range(cols.shape[0]):
        occ = cols[r] >= 0
        pairs = sorted(zip(cols[r][occ].tolist(),
                           np.round(vals[r][occ], 4).tolist()))
        assert len(pairs) == count[r]
        out.append(pairs)
    return out


def _random_stream(rng, r, ip_cap, n_cols):
    keys = rng.integers(0, n_cols, (r, ip_cap)).astype(np.int32)
    pad = rng.random((r, ip_cap)) < 0.3
    keys = np.where(pad, -1, keys)
    vals = np.where(pad, 0, rng.standard_normal((r, ip_cap))).astype(np.float32)
    return keys, vals


@pytest.mark.parametrize("r,ip_cap,n_cols,table_cap", [
    (4, 16, 8, 16), (2, 32, 64, 64), (8, 8, 4, 8), (1, 64, 16, 32),
])
def test_hash_accum_kernel_matches_jax_engine(r, ip_cap, n_cols, table_cap):
    rng = np.random.default_rng(0)
    keys, vals = _random_stream(rng, r, ip_cap, n_cols)
    kc, kv, kn = hash_accumulate(jnp.asarray(keys), jnp.asarray(vals),
                                 table_cap, interpret=True)
    jc, jv, jn = phases.accumulate_hash(jnp.asarray(keys), jnp.asarray(vals),
                                        table_cap)
    got = _as_sorted_pairs(np.asarray(kc), np.asarray(kv), np.asarray(kn))
    # jax engine emits sorted prefix; rebuild pairs the same way
    expect = []
    jc, jv, jn = np.asarray(jc), np.asarray(jv), np.asarray(jn)
    for i in range(r):
        expect.append(sorted(zip(jc[i, :jn[i]].tolist(),
                                 np.round(jv[i, :jn[i]], 4).tolist())))
    assert got == expect


@pytest.mark.parametrize("r,ip_cap,n_cols,table_cap,out_cap", [
    (4, 16, 8, 16, 8), (2, 32, 64, 64, 32), (8, 8, 4, 8, 4),
])
def test_hash_accum_sorted_matches_scan_engine(r, ip_cap, n_cols, table_cap,
                                               out_cap):
    """The fused-engine kernel branch (kernel table + XLA sort + trim) is
    bit-identical to the scan engine's sorted trimmed output — the
    contract `phases.fused_hash_sorted` relies on when the backend
    resolves to pallas/interpret (TPU)."""
    rng = np.random.default_rng(5)
    keys, vals = _random_stream(rng, r, ip_cap, n_cols)
    kc, kv, kn = hash_accumulate_sorted(jnp.asarray(keys), jnp.asarray(vals),
                                        table_cap, out_cap, interpret=True)
    jc, jv, jn = phases.fused_hash_sorted(jnp.asarray(keys),
                                          jnp.asarray(vals),
                                          table_cap, out_cap, kernel="xla")
    np.testing.assert_array_equal(np.asarray(kn), np.asarray(jn))
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(jc))
    np.testing.assert_allclose(np.asarray(kv), np.asarray(jv),
                               rtol=1e-6, atol=1e-6)


def test_fused_spgemm_through_interpret_kernel(monkeypatch):
    """End-to-end: REPRO_KERNEL_BACKEND=interpret routes the fused engine
    through the Pallas Algorithm-4 kernel (the TPU branch, interpreted on
    CPU) — results must stay bit-exact vs the two-pass hash engine."""
    from repro.core import executor
    from repro.core.spgemm import spgemm
    from repro.sparse.formats import csr_from_dense

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert executor._fused_kernel_mode(np.dtype(np.float32).str, 64) \
        == "interpret"
    rng = np.random.default_rng(7)
    x = np.where(rng.random((12, 12)) < 0.3,
                 rng.integers(1, 5, (12, 12)), 0).astype(np.float32)
    a = csr_from_dense(x)
    fu = spgemm(a, a, engine="fused_hash", row_chunk=8)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    ha = spgemm(a, a, engine="hash", row_chunk=8)
    nnz = fu.info["nnz_c"]
    assert nnz == ha.info["nnz_c"]
    np.testing.assert_array_equal(
        np.asarray(fu.c.indptr), np.asarray(ha.c.indptr))
    np.testing.assert_array_equal(
        np.asarray(fu.c.indices)[:nnz], np.asarray(ha.c.indices)[:nnz])
    np.testing.assert_array_equal(
        np.asarray(fu.c.data)[:nnz], np.asarray(ha.c.data)[:nnz])


def test_hash_accum_kernel_duplicate_keys_accumulate():
    keys = jnp.asarray([[3, 3, 3, -1]], jnp.int32)
    vals = jnp.asarray([[1.0, 2.0, 4.0, 9.0]], jnp.float32)
    cols, out, cnt = hash_accumulate(keys, vals, 8, interpret=True)
    assert int(cnt[0]) == 1
    occ = np.asarray(cols[0]) >= 0
    np.testing.assert_allclose(np.asarray(out[0])[occ], [7.0])


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_hash_accum_equals_segment_sum(seed):
    """Unordered (col → Σ val) content equals a segment-sum ground truth."""
    rng = np.random.default_rng(seed)
    keys, vals = _random_stream(rng, 2, 16, 8)
    cols, out, cnt = hash_accumulate(jnp.asarray(keys), jnp.asarray(vals),
                                     16, interpret=True)
    for r in range(2):
        truth = {}
        for k, v in zip(keys[r], vals[r]):
            if k >= 0:
                truth[int(k)] = truth.get(int(k), 0.0) + float(v)
        occ = np.asarray(cols[r]) >= 0
        got = dict(zip(np.asarray(cols[r])[occ].tolist(),
                       np.asarray(out[r])[occ].tolist()))
        assert set(got) == set(truth)
        for k in truth:
            np.testing.assert_allclose(got[k], truth[k], rtol=1e-5, atol=1e-5)
