"""A CPU rehearsal of run.py for every cell at tiny size: the last
line's keys and the metrics each mode must print. And the refusal: a
CPU run without ``--rehearse`` exits non-zero and prints no result."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

RUN = [sys.executable, os.path.join(manifest.HERE, "run.py")]
CELLS = manifest.names(manifest.benchmark()["workloads"])


def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(RUN + list(args), env=env, cwd=manifest.ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_prints_the_contracts_line(name, trace):
    proc = _run("--workload", name, "--seed", "3", "--seconds", "4",
                "--trace", str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["detail"]["rehearsal"]
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    cell = manifest.cell(name)
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        allowed = set(manifest.names(cell["per_layer"]))
        assert set(line["metrics"]) <= allowed
        assert line["metrics"]["compiles_in_window"]["value"] >= 0
    else:
        assert set(line["metrics"]) == set(manifest.names(cell["end_to_end"]))
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_cpu_run_is_refused_without_the_flag():
    proc = _run("--workload", CELLS[0], "--seed", "0", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not proc.stdout.strip()
