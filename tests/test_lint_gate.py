"""The lint gate as a tier-1 test: the shipped package must pass
`gmtpu lint --fail-on warn` (scripts/lint_gate.py), so a PR that
introduces a GT01..GT06 hazard without a waiver fails the suite the
same way it would fail CI."""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO_ROOT, "scripts", "lint_gate.py")


def test_lint_gate_passes_on_shipped_tree():
    # --no-spmd-smoke / --no-dataflow-smoke / --no-chaos-smoke /
    # --no-telemetry-smoke / --no-fleet-smoke / --no-wire-smoke:
    # those invariants already run in-process in this
    # same tier-1 suite (tests/test_analysis_spmd.py dirty-fixture
    # replays for every SPMD rule; tests/test_analysis_dataflow.py
    # dirty/clean fixtures for every dataflow rule plus the SARIF
    # provenance-chain assertion; tests/test_faults.py chaos
    # regression; tests/test_telemetry.py trace/scrape/gap checks;
    # tests/test_fleet.py kill-mid-burst failover + subscription
    # re-home across an owner kill (TestRehome); tests/test_wire.py
    # columnar parity + one-encode fan-out); repeating them in a cold
    # subprocess would only re-pay jax startup + kernel compiles
    # against the suite's wall-clock budget. All smokes still guard
    # standalone `python scripts/lint_gate.py` CI runs.
    r = subprocess.run([sys.executable, GATE, "--no-spmd-smoke",
                        "--no-dataflow-smoke", "--no-chaos-smoke",
                        "--no-telemetry-smoke", "--no-fleet-smoke",
                        "--no-rehome-smoke", "--no-approx-smoke",
                        "--no-wire-smoke"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, (
        f"lint gate failed:\n{r.stdout}\n{r.stderr}")
    assert "0 finding(s)" in r.stdout


def test_lint_gate_json_mode():
    import json

    r = subprocess.run([sys.executable, GATE, "--format", "json"],
                       capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout)
    assert doc["active"] == 0
    # the shipped tree documents its deliberate f64 paths via waivers
    assert doc["waived"] >= 1
