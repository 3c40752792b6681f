"""AIA ranged indirect gather — the paper's Fig. 2 primitive on TPU.

Semantics (paper §IV-C): given index array ``b`` and data array ``a``, serve
``a[b[i]·R] … a[b[i]·R + R − 1]`` for i = 0..N−1 as **one bulk stream**
instead of 2N processor⇄memory round trips.

TPU mapping: ``b`` lives in SMEM, the scalar core's memory, where it
programs DMAs instead of being dereferenced by the compute core.  Ranged
gathers (``R`` a multiple of 8) take it as a scalar-prefetch operand that
``BlockSpec.index_map`` reads to program each grid step's HBM→VMEM DMA,
pipelined by Pallas.  The row gather (``gather_rows``, any row id) streams
it through SMEM in blocks of ``IDX_BLOCK`` ids — SMEM holds 1 MiB, too
little for a whole chunk's index stream — and issues one DMA descriptor
per row.  A grid step starts all of its rows' copies before it waits on
any, so a whole step's rows are in flight at once: as many as fit its
double-buffered output block in ``OUT_VMEM_BUDGET`` (``rows_per_step``),
1024 rows of one 128-word tile row, fewer as rows widen, at least 8.  A
row costs then about its descriptor's issue, not a DMA round trip.  Either
way the DMA engine resolves the indirection near memory — the request
consolidation AIA performs in the HBM base die.

Tiling rules of the TPU compiler shape both paths.  HBM arrays are tiled
(8 sublanes × 128 lanes of 32-bit words), and:

* a DMA may start at any row only where the array is one lane tile wide.
  So rows are packed into 32-bit words (16- and 8-bit dtypes), padded to
  ``padded_width`` (a multiple of 128 lanes — a no-op for callers that
  place their operands pre-padded, as the SpGEMM executor does) and viewed
  as ``k`` consecutive 128-lane rows each; one DMA moves a row's ``k``
  tile rows, and the result is trimmed back;
* a ``BlockSpec`` block needs a multiple of 8 rows, so ranged gathers with
  ``R % 8 != 0`` (``R = 1``: the CSR row gather ``rpt_B[col_A[j]]`` → row
  of B) are served as per-row DMAs of the expanded row ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

LANES = 128
SUBLANES = 8
IDX_BLOCK = 1024         # row ids per SMEM block (1-D arrays tile by 1024)
MAX_PREFETCH_IDS = 1 << 16  # range ids the ranged gather prefetches to SMEM
OUT_VMEM_BUDGET = 1 << 20   # bytes of the row gather's double-buffered output
UNROLL = 32                 # row copies started (and waited) per loop trip


def padded_width(d: int) -> int:
    """Row width rounded up to the 128-lane tile a row DMA must cover."""
    return max(-(-int(d) // LANES), 1) * LANES


def _pad_lanes(x: jax.Array) -> jax.Array:
    d = x.shape[1]
    dp = padded_width(d)
    return x if dp == d else jnp.pad(x, ((0, 0), (0, dp - d)))


def _as_words(x: jax.Array) -> jax.Array:
    """(n, d) of any dtype → (n, ⌈d·itemsize/4⌉) 32-bit words (bit-exact)."""
    per = 4 // x.dtype.itemsize
    if per == 1:
        return x
    n, d = x.shape
    dw = -(-d // per)
    xe = jnp.pad(x, ((0, 0), (0, dw * per - d)))
    return jax.lax.bitcast_convert_type(xe.reshape(n, dw, per), jnp.uint32)


def _from_words(w: jax.Array, like: jax.Array, d: int) -> jax.Array:
    """Inverse of ``_as_words`` on gathered rows, trimmed to ``d`` columns."""
    per = 4 // like.dtype.itemsize
    if per == 1:
        return w[:, :d]
    return jax.lax.bitcast_convert_type(
        w[:, :-(-d // per)], like.dtype).reshape(w.shape[0], -1)[:, :d]


def _copy_kernel(idx_ref, x_ref, o_ref):
    # The gather already happened at DMA time (index_map); just stream out.
    o_ref[...] = x_ref[...]


def aia_ranged_gather(x: jax.Array, idx: jax.Array, r: int = 1,
                      interpret: bool | None = None) -> jax.Array:
    """out[i·R:(i+1)·R, :] = x[idx[i]·R : idx[i]·R+R, :].

    x:   (n_blocks·R, d) data array (HBM).
    idx: (N,) int32 block indices (the paper's ``b``; prefetched to SMEM).
    """
    interpret = resolve_interpret(interpret)
    if r % SUBLANES or idx.shape[0] > MAX_PREFETCH_IDS:
        rows = (idx.astype(jnp.int32)[:, None] * r
                + jnp.arange(r, dtype=jnp.int32)[None, :]).reshape(-1)
        return gather_rows_any(x, rows, interpret=interpret)
    return _ranged_gather(x, idx, r, interpret)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def _ranged_gather(x, idx, r: int, interpret: bool):
    n = idx.shape[0]
    xp = _pad_lanes(_as_words(x))
    dp = xp.shape[1]
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((r, dp),
                                   lambda i, idx_ref: (idx_ref[i], 0))],
            out_specs=pl.BlockSpec((r, dp), lambda i, idx_ref: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n * r, dp), xp.dtype),
        interpret=interpret,
        name="aia_ranged_gather",
    )(idx, xp)
    return _from_words(out, x, x.shape[1])


def rows_per_step(k: int) -> int:
    """Row copies in flight per grid step, for rows of ``k`` tile rows.

    The largest power of two that divides ``IDX_BLOCK`` and keeps the
    output block — ``rows·k`` tile rows of 128 32-bit words, double-buffered
    by Pallas — within ``OUT_VMEM_BUDGET``; never fewer than ``SUBLANES``.
    """
    rows = IDX_BLOCK
    while rows > SUBLANES and 2 * rows * k * LANES * 4 > OUT_VMEM_BUDGET:
        rows //= 2
    return rows


def gather_rows(x: jax.Array, idx: jax.Array, rows_per_block: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """out[i] = x[idx[i]] with idx grouped ``rows_per_block`` at a time.

    Each grid step starts one DMA descriptor per row of its group (the AIA
    "switching network" role), all of them before it waits on any, so the
    whole group's copies are in flight at once, and emits them
    contiguously.  ``rows_per_block=None`` takes ``rows_per_step(k)`` for
    the row's ``k`` tile rows: 1024 rows in flight for rows of one tile row
    (up to 128 32-bit words), 512 for two, halving as rows widen, at least
    8.  idx may have any length (it is padded with row 0 to the
    ``IDX_BLOCK`` multiple the SMEM staging needs, and the output trimmed
    back); every id must be a valid row.  Rows narrower than the 128-lane
    tile are padded for the DMA and trimmed on the way out.
    """
    return _gather_rows(x, idx, rows_per_block, resolve_interpret(interpret))


def _unrolled_loop(n: int, f) -> None:
    """``f(i)`` for i in [0, n), ``UNROLL`` calls per trip of a loop (Mosaic
    lowers a ``fori_loop`` only fully unrolled or not at all).  ``n`` and
    ``UNROLL`` are powers of two, so the trips cover [0, n) exactly."""
    u = min(UNROLL, n)

    def trip(j, carry):
        for i in range(u):
            f(j * u + i)
        return carry

    jax.lax.fori_loop(0, n // u, trip, 0)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def _gather_rows(x, idx, rows_per_block: int | None, interpret: bool):
    n = idx.shape[0]
    xp = _pad_lanes(_as_words(x))
    k = xp.shape[1] // LANES  # 128-lane tile rows per gathered row
    rpb = rows_per_step(k) if rows_per_block is None else rows_per_block
    assert IDX_BLOCK % rpb == 0, (IDX_BLOCK, rpb)
    n_pad = -(-n // IDX_BLOCK) * IDX_BLOCK
    if n_pad > n:
        idx = jnp.concatenate([idx, jnp.zeros(n_pad - n, idx.dtype)])
    steps_per_block = IDX_BLOCK // rpb

    def kernel(idx_ref, x_hbm, o_ref):
        base = (pl.program_id(0) % steps_per_block) * rpb

        def body(sem):
            def start(r):
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(idx_ref[base + r] * k, k), :],
                    o_ref.at[pl.ds(r * k, k), :], sem).start()

            def wait(r):
                # Every copy moves k tile rows, so one descriptor of that
                # shape waits for any one of them on the shared semaphore.
                pltpu.make_async_copy(x_hbm.at[pl.ds(0, k), :],
                                      o_ref.at[pl.ds(0, k), :], sem).wait()

            _unrolled_loop(rpb, start)
            _unrolled_loop(rpb, wait)

        pl.run_scoped(body, pltpu.SemaphoreType.DMA)

    out = pl.pallas_call(
        kernel,
        grid=(n_pad // rpb,),
        in_specs=[
            # the index stream, staged HBM→SMEM one IDX_BLOCK at a time
            pl.BlockSpec((IDX_BLOCK,), lambda i: (i // steps_per_block,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((rpb * k, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad * k, LANES), xp.dtype),
        interpret=interpret,
        name="aia_gather_rows",
    )(idx, xp.reshape(-1, LANES))
    out = out.reshape(n_pad, k * LANES)
    return _from_words(out if n_pad == n else out[:n], x, x.shape[1])


def gather_rows_any(x: jax.Array, idx: jax.Array,
                    rows_per_block: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """``x[idx]`` for any ``idx``: clips out-of-range ids, then gathers.

    The convenience wrapper shared by the SpGEMM executor's ``gather="aia"``
    backend and ``sparse.ops.csr_spmm``.
    """
    idx = jnp.clip(idx, 0, x.shape[0] - 1).astype(jnp.int32)
    return gather_rows(x, idx, rows_per_block, interpret=interpret)
