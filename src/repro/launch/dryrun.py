"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` asks for 512 host devices (``XLA_FLAGS``) before jax first
touches a backend — jax locks the device count at first init, and the
production meshes need 512.  Importing this module changes nothing.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # 40-cell single-pod campaign
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
memory analysis, cost analysis, collective schedule and roofline terms —
benchmarks/roofline.py and EXPERIMENTS.md read from there.
"""
import argparse
import dataclasses
import json
import os
import pathlib
import time
import traceback

import jax

from ..configs import ARCH_IDS, SHAPES, get_config, shape_cells
from .analysis import roofline_from_compiled
from .mesh import make_production_mesh
from .specs import build_cell

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, save: bool = True,
             opts=None) -> dict:
    from .specs import PerfOptions
    opts = opts or PerfOptions()
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if opts.tag() != "base":
        mesh_name += "__" + opts.tag()
    chips = mesh.devices.size
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, opts=opts)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.step_fn, donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    roof = roofline_from_compiled(arch, shape, mesh_name, chips, cfg,
                                  compiled)
    try:
        mem = compiled.memory_analysis()
        mem_dict = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception:
        mem_dict = {}
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_dict,
        "roofline": roof.to_dict(),
    }
    if verbose:
        r = roof
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"flops {r.hlo_flops:.3e} bytes {r.hlo_bytes:.3e} "
              f"coll {r.collective_bytes:.3e} | "
              f"terms c={r.compute_s * 1e3:.2f}ms m={r.memory_s * 1e3:.2f}ms "
              f"x={r.collective_s * 1e3:.2f}ms -> {r.dominant} | "
              f"roofline_frac {r.roofline_fraction:.3f}")
        if mem_dict:
            print(f"    memory_analysis: {mem_dict}")
        print(f"    collectives: { {k: f'{v:.3e}' for k, v in r.collectives.items()} }")
    if save:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        path = ART_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
        path.write_text(json.dumps(out, indent=1))
    return out


def force_host_devices(n: int = 512) -> None:
    """Ask XLA's CPU backend for ``n`` devices, keeping any other flags.
    Takes effect only before jax first initializes a backend."""
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())


def main() -> None:
    from .specs import PerfOptions
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--decode-kernel", default="ref",
                    choices=["ref", "fused_ref"])
    ap.add_argument("--bf16-grads", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--coherence", default="none",
                    choices=["none", "eager", "numapte"])
    ap.add_argument("--remat", default="full", choices=["full", "dots"])
    ap.add_argument("--compress-pod-grads", action="store_true")
    args = ap.parse_args()
    opts = PerfOptions(decode_kernel=args.decode_kernel,
                       bf16_grads=args.bf16_grads,
                       seq_parallel=args.seq_parallel,
                       coherence=args.coherence,
                       remat=args.remat,
                       compress_pod_grads=args.compress_pod_grads)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in shape_cells(arch):
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        try:
            run_cell(arch, shape, multi_pod=args.multi_pod, opts=opts)
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print(f"\nFAILED {len(failures)}/{len(cells)} cells:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(cells)} cells compiled OK")


if __name__ == "__main__":
    main()
