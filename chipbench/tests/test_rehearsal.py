"""Every driver end to end at tiny size on the CPU: the command line, the
control flow and the last line. The numbers mean nothing and are written
nowhere; the platform says "cpu"."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import harness
from conftest import rehearsal_manifest

CELLS = [w["name"] for w in rehearsal_manifest()["workloads"]]


def _run(script: str, manifest_path: str, *args: str):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, script),
         "--rehearsal", manifest_path, "--seed", "3", *args],
        capture_output=True, text=True, timeout=280, env=env,
        cwd=harness.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(x) for x in proc.stdout.strip().splitlines()]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_ends_in_a_well_formed_last_line(cell, trace, rehearsal_path):
    line = _run("run.py", rehearsal_path, "--workload", cell,
                "--seconds", "2.0", "--trace", str(trace))[-1]
    manifest = harness.Cell(rehearsal_manifest(), cell)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= manifest.chips
    assert "memory_peak_bytes" in dev
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in manifest.metrics(group)}
    assert line["metrics"], line
    for name, m in line["metrics"].items():
        assert declared[name] == m["unit"]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    else:
        assert set(line["metrics"]) == set(declared)
        assert line["metrics"]["setup_s"]["value"] > 0


def test_knee_sweep_prints_a_row_per_rate(rehearsal_path):
    rows = _run("sweep_knee.py", rehearsal_path, "--workload",
                "gpt2s-serve-chat", "--rates", "4,8", "--seconds", "2")
    assert [r["rate_per_s"] for r in rows[1:]] == [4.0, 8.0]
    assert all(r["failed"] == 0 and r["engine_step_ms"] > 0
               for r in rows[1:])


def test_no_chip_is_an_error_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", "gpt2s-train-1chip", "--seed", "0", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        cwd=harness.REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no CPU mode" in proc.stderr
