"""Run one benchmark cell on the TPU the process finds.

    python3 benchmarks/chip/run.py --workload qwen3_14b.decode_long \
        --seed 7 --seconds 30 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  The last line of standard
output is the JSON result; the numbers that decide ``correct`` are also
the last lines of standard error.  Without a TPU, with fewer chips than
the cell asks for, or on a device kind with no published peaks in
``peaks.py``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices; exits non-zero when JAX finds no
    TPU or too few.  Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"run.py: no TPU: JAX found {len(devices)} {d.platform} "
                 f"device(s) ({d.device_kind})")
    if len(devices) < chips:
        sys.exit(f"run.py: the cell needs {chips} TPU chips, JAX found "
                 f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` at the checkout's root (a fixed path,
    so every run of the checkout after the first finds its programs)."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from benchmarks.chip import harness
    from benchmarks.chip.peaks import UnknownDevice, peaks_for
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    devices = require_tpu(cell.entry["chips"])
    try:
        peaks = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        sys.exit(f"run.py: {e}")
    enable_compile_cache()
    harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                     devices, T_START, peaks)


if __name__ == "__main__":
    main()
