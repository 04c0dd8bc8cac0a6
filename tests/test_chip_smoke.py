"""chip_smoke.py: refuses any platform but a TPU, and its phases agree
with the host oracle at a tiny size (here on CPU, kernels interpreted)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_cpu_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("seed", [0, 1])
def test_phases_match_host_oracle(tmp_path, seed):
    rows = 1 << 15
    qx, qy = chip_smoke.query_points(seed)
    store, src, batch = chip_smoke.build_store(str(tmp_path), rows, seed)
    oracle = chip_smoke.host_oracle(batch, qx, qy)
    assert oracle["count"] > 0
    res = chip_smoke.load(src, rows)
    assert res["rows"] == rows
    direct = chip_smoke.direct_phase(src, oracle, qx, qy)
    served = chip_smoke.serve_phase(store, qx, qy, direct)
    assert served["dispatches"] >= 1


@pytest.mark.parametrize("seed", [97, 98])
def test_kernel_gates_match_oracles(seed):
    # the chip's 2^20 rows, 2^15 points and 256^2 grid cut to a size
    # the interpreted kernels run in seconds; 32^2 cells keep a
    # z-ordered tile under the sparse density kernel's cell cap
    gates = chip_smoke.kernel_gates(rows=1 << 14, pip_points=1 << 11,
                                    grid=32, seed=seed)
    assert gates == {"pip_layer": True, "pip_assign": True,
                     "density_zsparse": True, "tube_pruned": True}
