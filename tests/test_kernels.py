"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import aia_gather as aia_k
from repro.kernels import spgemm_bsr as bsr_k
from repro.kernels import topk_spmm as topk_k


# ---------------------------------------------------------------------------
# aia_ranged_gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_blocks,r,d,n_idx", [
    (8, 1, 128, 16), (8, 2, 128, 5), (16, 4, 256, 32), (4, 8, 8, 3),
])
def test_aia_ranged_gather_sweep(dtype, n_blocks, r, d, n_idx):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n_blocks * r, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n_blocks, n_idx), jnp.int32)
    got = aia_k.aia_ranged_gather(x, idx, r, interpret=True)
    expect = ref.aia_ranged_gather(x, idx, r)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_aia_gather_repeated_and_boundary_indices():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    idx = jnp.asarray([0, 31, 31, 0, 15], jnp.int32)
    got = aia_k.aia_ranged_gather(x, idx, 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[[0, 31, 31, 0, 15]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_rows_manual_dma(dtype):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((40, 128)), dtype)
    idx = jnp.asarray(rng.integers(0, 40, 24), jnp.int32)
    got = aia_k.gather_rows(x, idx, rows_per_block=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[np.asarray(idx)])


# ---------------------------------------------------------------------------
# bsr_spmm
# ---------------------------------------------------------------------------

def _random_bsr(rng, n_brows, n_bcols, bs, avg_blocks):
    rows = [sorted(rng.choice(
        n_bcols, size=min(n_bcols, 1 + rng.integers(0, 2 * avg_blocks)),
        replace=False).tolist()) for _ in range(n_brows)]
    rowptr = np.concatenate([[0], np.cumsum([len(r) for r in rows])]).astype(np.int32)
    colidx = np.concatenate(rows).astype(np.int32)
    blocks = rng.standard_normal((len(colidx), bs, bs)).astype(np.float32)
    return rowptr, colidx, blocks


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_brows,n_bcols,bs,d", [
    (4, 6, 8, 16), (8, 8, 16, 32), (3, 10, 8, 128), (1, 2, 8, 8),
])
def test_bsr_spmm_sweep(dtype, n_brows, n_bcols, bs, d):
    rng = np.random.default_rng(3)
    rowptr, colidx, blocks = _random_bsr(rng, n_brows, n_bcols, bs, 2)
    b = rng.standard_normal((n_bcols * bs, d)).astype(np.float32)
    max_bpr = int((rowptr[1:] - rowptr[:-1]).max())
    got = bsr_k.bsr_spmm(
        jnp.asarray(rowptr), jnp.asarray(colidx),
        jnp.asarray(blocks, dtype), jnp.asarray(b, dtype),
        max_blocks_per_row=max_bpr, interpret=True,
    )
    expect = ref.bsr_spmm(jnp.asarray(rowptr), jnp.asarray(colidx),
                          jnp.asarray(blocks, dtype), jnp.asarray(b, dtype))
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32), rtol=rtol, atol=1e-2)


def test_bsr_spmm_empty_row():
    """A block-row with zero blocks must produce zeros (ragged-tail masking)."""
    bs, d = 8, 16
    rowptr = jnp.asarray([0, 2, 2, 3], jnp.int32)  # row 1 empty
    colidx = jnp.asarray([0, 1, 1], jnp.int32)
    rng = np.random.default_rng(4)
    blocks = jnp.asarray(rng.standard_normal((3, bs, bs)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2 * bs, d)), jnp.float32)
    got = bsr_k.bsr_spmm(rowptr, colidx, blocks, b, max_blocks_per_row=2,
                         interpret=True)
    expect = ref.bsr_spmm(rowptr, colidx, blocks, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-5)
    assert np.abs(np.asarray(got)[bs:2 * bs]).max() == 0.0


# ---------------------------------------------------------------------------
# topk_spmm (Eq. 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,k,dff,d", [(4, 2, 16, 8), (16, 4, 64, 128), (3, 8, 32, 16)])
def test_topk_spmm_sweep(dtype, n, k, dff, d):
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.standard_normal((n, k)), dtype)
    idx = jnp.asarray(rng.integers(0, dff, (n, k)), jnp.int32)
    w2 = jnp.asarray(rng.standard_normal((dff, d)), dtype)
    got = topk_k.topk_spmm(vals, idx, w2, interpret=True)
    expect = ref.topk_spmm(vals, idx, w2)
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=rtol,
                               atol=1e-2)


def test_topk_spmm_duplicate_indices_accumulate():
    """Same W2 row selected twice for a token must be added twice."""
    vals = jnp.asarray([[1.0, 2.0]], jnp.float32)
    idx = jnp.asarray([[3, 3]], jnp.int32)
    w2 = jnp.asarray(np.eye(8, 4, k=-3), jnp.float32)  # row 3 -> e0
    got = topk_k.topk_spmm(vals, idx, w2, interpret=True)
    np.testing.assert_allclose(np.asarray(got), [[3.0, 0, 0, 0]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_tiles,kb,tile,block,d", [
    (2, 2, 8, 16, 32), (4, 3, 8, 128, 64), (1, 1, 8, 8, 8),
])
def test_block_topk_spmm_sweep(dtype, n_tiles, kb, tile, block, d):
    rng = np.random.default_rng(6)
    n_blocks = kb + 2
    h = jnp.asarray(rng.standard_normal((n_tiles, kb, tile, block)), dtype)
    bidx = jnp.asarray(
        np.stack([rng.choice(n_blocks, kb, replace=False) for _ in range(n_tiles)]),
        jnp.int32)
    w2 = jnp.asarray(rng.standard_normal((n_blocks * block, d)), dtype)
    got = topk_k.block_topk_spmm(h, bidx, w2, block=block, interpret=True)
    expect = ref.block_topk_spmm(h, bidx, w2, block)
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32), rtol=rtol, atol=5e-2)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------

def test_ops_backends_agree():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
    a = ops.aia_ranged_gather(x, idx, 1, backend="xla")
    b = ops.aia_ranged_gather(x, idx, 1, backend="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("platform,override,expect", [
    ("tpu", None, False),            # TPU default: compiled
    ("tpu", "interpret", True),      # asked for
    ("tpu", "xla", RuntimeError),    # never interpret silently on TPU
    ("cpu", None, True),             # off-TPU the interpreter is the only way
    ("cpu", "pallas", False),
])
def test_resolve_interpret_policy(monkeypatch, platform, override, expect):
    from repro.kernels import backend

    monkeypatch.setattr(backend.jax, "default_backend", lambda: platform)
    if override is None:
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", override)
    assert backend.resolve_interpret(True) is True
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="Pallas kernel"):
            backend.resolve_interpret(None)
    else:
        assert backend.resolve_interpret(None) is expect
