"""The peaks table refuses a device it does not know, and the command
refuses to run anywhere but on a TPU, printing no result."""
import os
import pathlib
import subprocess
import sys

import pytest

from benchmarks.chip.peaks import PEAKS, UnknownDevice, peaks_for

ROOT = pathlib.Path(__file__).resolve().parents[3]


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_kind_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
    assert "cpu" not in PEAKS


def _run(args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmarks/chip/run.py",
                           *args], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_without_a_result():
    r = _run(["--workload", "qwen3_14b.decode_long", "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_unknown_device_kind_exits_without_a_result(tmp_path):
    # the harness's own check, with the device kind of the CPU
    code = (
        "import sys; sys.argv = ['run.py', '--workload', "
        "'qwen3_14b.decode_long', '--seed', '1', '--seconds', '1'];"
        "sys.path.insert(0, 'benchmarks/chip');"
        "import run; run.require_tpu = lambda chips: __import__('jax')"
        ".devices()[:chips]; run.main()")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no published peaks" in r.stderr
    assert '"correct"' not in r.stdout
