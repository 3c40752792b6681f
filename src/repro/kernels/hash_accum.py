"""Algorithm 4 (InsertIntoTable/AddInTable) as a Pallas TPU kernel.

One grid step = one output row of C: the row's padded intermediate-product
stream (keys, vals) is consumed sequentially against a linear-probing hash
table — the TPU realization of the paper's Table-I per-group kernels
(across-row parallelism comes from the grid, replacing PWPR/TBPR thread
blocks; no atomics needed because the per-row stream is sequential,
DESIGN.md §2 adaptation #1/#2).

Memory placement follows the TPU compiler's rules for scalar code:

* The table lives in **SMEM** scratch — the only memory the scalar core
  may load from and store to one word at a time.  SMEM holds 1 MiB, so a
  table of ``table_cap`` slots (keys + values + a slot log) fits up to
  ``MAX_TABLE_CAP`` = 65536 slots; every Table-I capacity (64, 1024, 8192)
  fits, and ``table_fits`` is the visible rule the executor routes larger
  group-3 tables by.
* The stream is DMA'd HBM→SMEM in chunks of up to ``STREAM_CHUNK`` words,
  and the finished table SMEM→HBM.  One-dimensional int32/float32 arrays
  are tiled by 1024 words in both memories, so the wrapper flattens the
  stream and the output with rows padded to 1024-word multiples.
* The table is cleared once, at the first grid step, and afterwards only
  the slots a row touched are restored (from the slot log): O(uniqueCount)
  per row instead of O(table_cap).

Emits the *unsorted* table + uniqueCount per row; column-index sorting
(Algorithm 5 step 3) stays in XLA (`jnp.sort` lowers to a sorting network),
matching the phase split of the paper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

MULTIPLIER = 2654435761
# The same multiplier as a wrapping int32: the low bits of the product (all
# a power-of-two table keeps) equal those of the uint32 product.
_MULT_I32 = MULTIPLIER - (1 << 32)
EMPTY = -1
_INT_MAX = jnp.int32(2**31 - 1)
WORD_TILE = 1024        # 1-D 32-bit arrays are tiled by 1024 words
STREAM_CHUNK = 4096     # stream words staged in SMEM per DMA
MAX_TABLE_CAP = 65536   # keys + values + slot log + stream chunks < 1 MiB


def table_fits(table_cap: int) -> bool:
    """Whether a ``table_cap``-slot table fits the kernel's SMEM budget."""
    return int(table_cap) <= MAX_TABLE_CAP


def _round_up(x: int, m: int) -> int:
    return max(-(-int(x) // m), 1) * m


def _hash_kernel(keys_hbm, vals_hbm, cols_hbm, out_hbm,
                 kbuf, vbuf, tkey, tval, slot_log, sems, *,
                 ip_cap, ip_pad, chunk, table_cap, t_pad):
    row = pl.program_id(0)
    mask = table_cap - 1

    @pl.when(row == 0)
    def _():
        def clear(i, c):
            tkey[i] = EMPTY
            tval[i] = jnp.float32(0)
            return c

        jax.lax.fori_loop(0, t_pad, clear, 0)

    def insert(i, count):
        key = kbuf[i]
        val = vbuf[i]
        pos0 = (key * _MULT_I32) & mask

        def cond(state):
            _, done, probes, _ = state
            return jnp.logical_and(jnp.logical_not(done), probes < table_cap)

        def body(state):
            pos, _, probes, cnt = state
            slot = tkey[pos]
            hit = slot == key
            empty = slot == EMPTY

            @pl.when(empty)
            def _():
                tkey[pos] = key
                slot_log[cnt] = pos

            @pl.when(hit | empty)
            def _():
                tval[pos] = tval[pos] + val

            done = hit | empty
            nxt = jnp.where(done, pos, (pos + 1) & mask)
            return nxt, done, probes + 1, cnt + empty.astype(jnp.int32)

        state = (pos0, key < 0, jnp.int32(0), count)
        return jax.lax.while_loop(cond, body, state)[3]

    def consume(c, count):
        base = row * ip_pad + c * chunk
        cps = [pltpu.make_async_copy(src.at[pl.ds(base, chunk)], dst,
                                     sems.at[j])
               for j, (src, dst) in enumerate(((keys_hbm, kbuf),
                                               (vals_hbm, vbuf)))]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()
        n_in = jnp.minimum(chunk, ip_cap - c * chunk)
        return jax.lax.fori_loop(0, n_in, insert, count)

    count = jax.lax.fori_loop(0, ip_pad // chunk, consume, jnp.int32(0))
    out = row * t_pad
    cps = [pltpu.make_async_copy(src, dst.at[pl.ds(out, t_pad)], sems.at[j])
           for j, (src, dst) in enumerate(((tkey, cols_hbm),
                                           (tval, out_hbm)))]
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()

    def restore(j, c):
        pos = slot_log[j]
        tkey[pos] = EMPTY
        tval[pos] = jnp.float32(0)
        return c

    jax.lax.fori_loop(0, count, restore, 0)


def hash_accumulate_sorted(keys: jax.Array, vals: jax.Array, table_cap: int,
                           out_cap: int, interpret: bool | None = None):
    """Kernel accumulation + Algorithm 5 step 3 (column-index sort) + trim.

    The fused-engine entry point: the per-row table comes back from the
    Pallas kernel in *probe order*; the XLA sort (a sorting network on TPU,
    matching the paper's bitonic phase split) moves the occupied slots to a
    column-sorted prefix, which is trimmed to the caller's ``out_cap``
    capacity bound (``out_cap`` ≥ uniqueCount must hold — the executor's
    plan-derived sizing guarantees it).

    Returns (cols (R, out_cap) int32 -1-padded, vals (R, out_cap), counts
    (R,) int32) — the same layout as ``phases.accumulate_hash`` trimmed to
    ``out_cap``, and bit-identical to it (same insertion order, same sort).
    """
    # Resolve the backend OUTSIDE the jitted body: ``interpret=None`` is a
    # static argument, so resolving it at trace time would bake the first
    # call's env-var state into the jit cache and silently ignore later
    # ``REPRO_KERNEL_BACKEND`` changes for same-shape calls.
    return _hash_accumulate_sorted(keys, vals, table_cap, out_cap,
                                   resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("table_cap", "out_cap",
                                             "interpret"))
def _hash_accumulate_sorted(keys: jax.Array, vals: jax.Array, table_cap: int,
                            out_cap: int, interpret: bool):
    tc, tv, cnt = hash_accumulate(keys, vals, table_cap, interpret=interpret)
    skey = jnp.where(tc == EMPTY, _INT_MAX, tc)
    order = jnp.argsort(skey, axis=1, stable=True)
    sc = jnp.take_along_axis(tc, order, axis=1)
    sv = jnp.take_along_axis(tv, order, axis=1)
    valid = jnp.arange(table_cap, dtype=jnp.int32)[None, :] < cnt[:, None]
    cols = jnp.where(valid, sc, EMPTY)[:, :out_cap]
    out = jnp.where(valid, sv, 0)[:, :out_cap]
    return cols, out, cnt


def hash_accumulate(keys: jax.Array, vals: jax.Array, table_cap: int,
                    interpret: bool | None = None):
    """Per-row Algorithm-4 accumulation.

    keys: (R, ip_cap) int32, -1 padded; vals: (R, ip_cap) float32.
    ``table_cap`` must be a power of two no larger than ``MAX_TABLE_CAP``.
    Returns (cols (R, table_cap) int32 EMPTY-padded — *unsorted*,
             vals (R, table_cap) float32, counts (R,) int32).
    """
    return _hash_accumulate(keys, vals, table_cap,
                            resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("table_cap", "interpret"))
def _hash_accumulate(keys: jax.Array, vals: jax.Array, table_cap: int,
                     interpret: bool):
    table_cap = int(table_cap)
    if table_cap & (table_cap - 1) or not table_fits(table_cap):
        raise ValueError(
            f"hash kernel table_cap must be a power of two <= "
            f"{MAX_TABLE_CAP}, got {table_cap}")
    if vals.dtype != jnp.float32:
        raise ValueError(f"hash kernel values are float32, got {vals.dtype}")
    r, ip_cap = keys.shape
    if r == 0:
        return (jnp.zeros((0, table_cap), jnp.int32),
                jnp.zeros((0, table_cap), jnp.float32),
                jnp.zeros((0,), jnp.int32))
    chunk = min(STREAM_CHUNK, _round_up(ip_cap, WORD_TILE))
    ip_pad = _round_up(ip_cap, chunk)
    t_pad = _round_up(table_cap, WORD_TILE)
    pad = ((0, 0), (0, ip_pad - ip_cap))
    flat_keys = jnp.pad(keys.astype(jnp.int32), pad,
                        constant_values=EMPTY).reshape(-1)
    flat_vals = jnp.pad(vals, pad).reshape(-1)
    kernel = functools.partial(_hash_kernel, ip_cap=ip_cap, ip_pad=ip_pad,
                               chunk=chunk, table_cap=table_cap, t_pad=t_pad)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    cols, out = pl.pallas_call(
        kernel,
        grid=(r,),
        in_specs=[hbm, hbm],
        out_specs=[hbm, hbm],
        out_shape=[
            jax.ShapeDtypeStruct((r * t_pad,), jnp.int32),
            jax.ShapeDtypeStruct((r * t_pad,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((chunk,), jnp.int32),
            pltpu.SMEM((chunk,), jnp.float32),
            pltpu.SMEM((t_pad,), jnp.int32),
            pltpu.SMEM((t_pad,), jnp.float32),
            pltpu.SMEM((table_cap,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # The table persists across rows (cleared at step 0, restored after
        # each row), so the grid runs in order on one core.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hash_accumulate",
    )(flat_keys, flat_vals)
    cols = cols.reshape(r, t_pad)[:, :table_cap]
    out = out.reshape(r, t_pad)[:, :table_cap]
    return cols, out, jnp.sum(cols != EMPTY, axis=1).astype(jnp.int32)
