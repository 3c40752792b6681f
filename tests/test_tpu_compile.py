"""The main-path Pallas kernels compile for a described TPU v5e.

No chip is attached: the TPU compiler compiles for ``v5e:2x2`` described
by ``jax.experimental.topologies``, at the shapes the chip smoke's matrices
produce (Economics and p2p-Gnutella04 at their published Table II row
counts).  Every case checks that the compiled program holds the kernel
(``tpu_custom_call``).  The topology is described inside a module fixture —
never at import — so only the worker that runs this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.graphs import table_ii_matrix
from repro.core.grouping import group_rows
from repro.core.phases import stream_width
from repro.kernels import aia_gather, hash_accum

ROW_CHUNK = 4096  # the executor's default row_chunk
SMOKE_MATRICES = {"Economics": 206_000, "p2p-Gnutella04": 10_876}
GCN_NODES, GCN_ENTRIES = 169_343, 2_325_906  # ogbn-arxiv; entries of A_hat


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plans():
    """(kb_cap, per-group (rows, a_cap, table_cap)) of each smoke matrix."""
    out = {}
    for name, rows in SMOKE_MATRICES.items():
        a = table_ii_matrix(name, seed=0, n_override=rows)
        plan = group_rows(a, a)
        row_nnz = np.asarray(a.row_nnz())
        groups = {}
        for g in range(4):
            members = plan.rows_of_group(g)
            if len(members):
                groups[g] = (len(members), int(row_nnz[members].max()),
                             plan.table_capacities[g])
        out[name] = (int(row_nnz.max()), groups)
    return out


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


@pytest.mark.parametrize("name", list(SMOKE_MATRICES))
def test_gather_rows_compiles_at_smoke_widths(one_chip, plans, name):
    """B's ELL planes at the matrix's kb_cap (placed lane-padded, as the
    executor's aia path does), one row chunk's index stream."""
    kb_cap, groups = plans[name]
    a_cap = max(a for _, a, _ in groups.values())
    width = aia_gather.padded_width(kb_cap)
    x = jax.ShapeDtypeStruct((SMOKE_MATRICES[name], width), jnp.int32,
                             sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROW_CHUNK * a_cap,), jnp.int32,
                               sharding=one_chip)
    text = _compile(lambda x, i: aia_gather.gather_rows_any(
        x, i, interpret=False), x, idx)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [128, 256])
def test_gather_rows_compiles_at_gcn_widths(one_chip, width):
    """The GCN aggregation's gathers at ogbn-arxiv's size: the 128 input
    features and the 256-wide hidden layers (float32), one id per stored
    entry of A_hat, at the rows in flight chosen for each width."""
    x = jax.ShapeDtypeStruct((GCN_NODES, width), jnp.float32,
                             sharding=one_chip)
    idx = jax.ShapeDtypeStruct((GCN_ENTRIES,), jnp.int32, sharding=one_chip)
    text = _compile(lambda x, i: aia_gather.gather_rows_any(
        x, i, interpret=False), x, idx)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("table_cap", [64, 1024, 8192, "group3", 32768,
                                       hash_accum.MAX_TABLE_CAP])
def test_hash_accumulate_sorted_compiles(one_chip, plans, table_cap):
    """Every Table-I capacity at the Economics plan's largest ip_cap; the
    group-3 table of p2p-Gnutella04 at its own ip_cap; and the
    larger group-3 tables ``table_fits`` admits, up to ``MAX_TABLE_CAP``,
    at that group's rows and ip_cap.  ip_cap is the padded stream width
    the executor hands the kernel (``phases.stream_width``)."""
    if table_cap == "group3" or table_cap > 8192:
        kb_cap, groups = plans["p2p-Gnutella04"]
        n_rows, a_cap, g3_cap = groups[3]
        if table_cap == "group3":
            table_cap = g3_cap
        rows = -(-n_rows // 8) * 8
    else:
        kb_cap, groups = plans["Economics"]
        a_cap = max(a for _, a, _ in groups.values())
        rows = ROW_CHUNK
    assert hash_accum.table_fits(table_cap)
    ip_cap = stream_width(a_cap * kb_cap)
    keys = jax.ShapeDtypeStruct((rows, ip_cap), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((rows, ip_cap), jnp.float32,
                                sharding=one_chip)
    out_cap = min(table_cap, 1024)
    text = _compile(lambda k, v: hash_accum.hash_accumulate_sorted(
        k, v, table_cap, out_cap, interpret=False), keys, vals)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("r,d", [(1, 8), (8, 8), (1, 300)])
def test_aia_ranged_gather_compiles(one_chip, r, d):
    x = jax.ShapeDtypeStruct((64 * r, d), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((5,), jnp.int32, sharding=one_chip)
    text = _compile(lambda x, i: aia_gather.aia_ranged_gather(
        x, i, r, interpret=False), x, idx)
    assert "tpu_custom_call" in text
