"""Emission contract of the repo's chip-facing scripts on a host with
no chip (this suite runs on the CPU): bench.py and chip_smoke.py exit
non-zero and print NO result — there is no cached number and no
fallback backend. The benchmark's own contract is ``benchmarks/tests``."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, script), *args],
        env=env, capture_output=True, text=True, timeout=170)


@pytest.mark.parametrize("script", ["bench.py"])
def test_bench_refuses_without_chip_no_metric_line(script):
    """No chip -> non-zero exit and not one JSON line on stdout (the
    removed behavior: a cached value first, again on failure, rc 0)."""
    res = _run(script)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not [ln for ln in res.stdout.splitlines()
                if ln.strip().startswith("{")], res.stdout


def test_chip_smoke_fails_on_cpu_without_verdict():
    res = _run("chip_smoke.py")
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok": true' not in res.stdout
    assert not res.stdout.strip()
