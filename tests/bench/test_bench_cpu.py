"""No silent CPU runs: without a TPU the harness prints no result."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec
from bench.spec import ROOT


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return True
    return False


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run("--workload", "dlrm-recmg.recmg_steady", "--seed", str(2**31 + 3),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no result" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_missing_device_kind_raises():
    assert spec.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
