"""chip_smoke.py phases at tiny sizes on CPU (interpret-mode kernels), its
refusal to run without a TPU, the four-device sharded phase on virtual
devices, and the service's batch-replay counter."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from repro.core import faults  # noqa: E402
from repro.serve import SpGEMMService  # noqa: E402
from repro.sparse.formats import csr_from_dense  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")


def test_main_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "no TPU" in err


def test_main_refuses_kernel_backend_override(interpret, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "REPRO_KERNEL_BACKEND" in err


@pytest.mark.parametrize("name", ["Economics", "p2p-Gnutella04"])
def test_phase_selfproduct_tiny(interpret, name):
    a = chip_smoke.table_ii(name, 192, seed=0)
    out = chip_smoke.phase_selfproduct(name, a, expect_kernels=False)
    assert out["nnz_c"] == chip_smoke.scipy_product(a).nnz
    # interpret mode lowers no compiled kernel; the chip run requires both
    assert not out["gather_compiled"] and not out["hash_compiled"]


def test_phase_mcl_tiny(interpret):
    g = chip_smoke.table_ii("p2p-Gnutella04", 128, seed=0)
    assert chip_smoke.phase_mcl(g, iters=2)["iterations"] >= 1


def test_phase_gnn_tiny(interpret):
    out = chip_smoke.phase_gnn(nodes=96, d_in=16, n_classes=4, hidden=24,
                               n_layers=2, steps=2, expect_kernels=False)
    assert len(out["losses"]) == 2 and np.all(np.isfinite(out["losses"]))


def test_phase_service_tiny(interpret):
    out = chip_smoke.phase_service(n=48, density=0.1, requests=8,
                                   tenants=4, patterns=2)
    assert out["batched_dispatches"] >= 1
    assert out["replayed"] == 0 and out["quarantined"] == 0


def test_sharded_phase_four_virtual_devices():
    """``--chips 4``'s phase on 4 forced CPU devices: bit-identical to one
    device (the phase itself raises otherwise)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", REPRO_KERNEL_BACKEND="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    script = ("import chip_smoke\n"
              "a = chip_smoke.table_ii('Economics', 256, seed=0)\n"
              "print('NNZ', chip_smoke.phase_sharded(a, 4)['nnz_c'])\n")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "bit_identical=True" in proc.stdout and "NNZ" in proc.stdout


def _same_pattern_batch(k):
    rng = np.random.default_rng(3)
    mask = rng.random((12, 12)) < 0.3
    mats = [csr_from_dense((mask * rng.standard_normal((12, 12)))
                           .astype(np.float32)) for _ in range(k)]
    return mats, csr_from_dense((mask * 1.5).astype(np.float32))


@pytest.mark.parametrize("fail", [False, True])
def test_service_replay_counter(fail):
    """0 on a clean batch; one per member when the batched dispatch fails
    and every member is replayed alone."""
    mats, b = _same_pattern_batch(3)
    svc = SpGEMMService(max_batch=3, max_wait=3600.0)
    if fail:
        with faults.fault_injection("dispatch_fail", times=1):
            tickets = [svc.submit("t", a, b) for a in mats]
    else:
        tickets = [svc.submit("t", a, b) for a in mats]
    assert all(t.done for t in tickets)
    st = svc.stats()
    assert st["batched_dispatches"] == 1
    assert st["replayed"] == (3 if fail else 0)
    assert st["quarantined"] == 0 and st["requests_completed"] == 3
