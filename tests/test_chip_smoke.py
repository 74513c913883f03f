"""``chip_smoke.py`` on the CPU: its rehearsal passes at a tiny size with
interpret-mode kernels, and without a TPU it refuses, printing no result.

Each run is a subprocess, so the script owns its process's devices (the
four-device rehearsal forces four host devices before JAX starts).
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _smoke(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)


def _no_result_line(stdout: str) -> bool:
    return not any(line.startswith('{"ok"') for line in stdout.splitlines())


@pytest.mark.parametrize("args", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_cpu_rehearsal_passes_every_phase(args):
    proc = _smoke("--cpu-rehearsal", *args)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "rehearsal passed" in proc.stdout
    assert "FAIL" not in proc.stdout
    assert _no_result_line(proc.stdout)


def test_refuses_without_a_tpu():
    proc = _smoke(timeout=300)
    assert proc.returncode != 0
    assert _no_result_line(proc.stdout)
    assert "needs a TPU" in proc.stderr
