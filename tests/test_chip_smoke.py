"""chip_smoke.py: the rehearsal keeps the script runnable, and the real
invocation cannot pass without a chip."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_passes_and_says_what_it_is():
    proc = _run("--rehearse", timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL:"), lines[0]
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    for phase in ("kernels", "train", "serve", "multichip-train",
                  "multichip-serve"):
        assert f"[{phase}] PASSED" in proc.stdout, phase


def test_real_invocation_fails_without_a_chip():
    proc = _run(timeout=120)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stdout
    # No result line: the last line is not the success object.
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
