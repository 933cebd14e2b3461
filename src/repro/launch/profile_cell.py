"""Per-computation profiler for dry-run cells (the perf-loop microscope).

    PYTHONPATH=src python -m repro.launch.profile_cell --arch X --shape Y \
        [--multi-pod] [--decode-kernel fused_ref] [--top 10]

Prints byte/flop/collective contributions per computation (trip-count
weighted) and the heaviest instructions inside the top computations.
"""
import argparse
import collections

import jax

from ..configs import ARCH_IDS, SHAPES
from . import hlo_analysis as H
from .dryrun import force_host_devices
from .mesh import make_production_mesh
from .specs import PerfOptions, build_cell


def profile(hlo: str, n_devices: int, top: int = 10) -> None:
    comps, entry = H.parse_module(hlo, n_devices)
    rows = collections.Counter()
    colls = collections.Counter()

    def trip_of(c):
        cc = comps.get(c)
        return max(1, cc.trip_const) if cc else 1

    def walk(name, mult, mode, sup):
        comp = comps.get(name)
        if comp is None:
            return
        if not sup:
            b = comp.fused_bytes() if mode == "fused" else (
                comp.dataflow_bytes() if mode == "dataflow" else 0)
            rows[(name, mode)] += b * mult
        for op, rb, n, *_ in comp.collectives:
            colls[(name, op, rb, n)] += mult
        conds = [c for c, k, _ in comp.callees if k == "cond"]
        bodies = [c for c, k, _ in comp.callees if k == "body"]
        tb = {b: trip_of(c) for c, b in zip(conds, bodies)}
        seen = set()
        for callee, kind, scoped in comp.callees:
            if (callee, kind) in seen:
                continue
            seen.add((callee, kind))
            if kind == "body":
                walk(callee, mult * tb.get(callee, 1), "dataflow", sup)
            elif kind == "cond":
                walk(callee, mult * trip_of(callee), "dataflow", sup)
            elif kind == "scalar":
                walk(callee, mult, "scalar", True)
            elif kind == "calls" and callee in comp.fusion_callees:
                walk(callee, mult, "fused", sup or scoped)
            else:
                walk(callee, mult, "dataflow", sup)

    walk(entry, 1.0, "dataflow", False)
    print(f"== top {top} byte contributors (per device):")
    for (name, mode), b in rows.most_common(top):
        print(f"  {b:12.3e}  {mode:9s} {name[:70]}")
        comp = comps[name]
        per = collections.Counter()
        for i in comp.instrs:
            key = (i.op, i.type_str[:40], i.scoped)
            if mode == "fused":
                per[key] += 0      # boundary model; show raw shapes anyway
                per[key] += comp._instr_bytes(i)
            else:
                per[key] += comp._instr_bytes(i) if not i.scoped else 0
        for k, v in per.most_common(3):
            if v > 0:
                print(f"        {v:11.3e} {k}")
    print("== collectives:")
    for (name, op, rb, n), mult in sorted(
            colls.items(), key=lambda kv: -kv[0][2] * kv[1])[:top]:
        print(f"  {op:20s} rb={rb:11.3e} n={n:4d} x{mult:7.0f} in {name[:50]}")


def main() -> None:
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=list(SHAPES), required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--decode-kernel", default="ref")
    ap.add_argument("--bf16-grads", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--coherence", default="none")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--top", type=int, default=8)
    args = ap.parse_args()
    opts = PerfOptions(decode_kernel=args.decode_kernel,
                       bf16_grads=args.bf16_grads,
                       seq_parallel=args.seq_parallel,
                       coherence=args.coherence,
                       remat=args.remat)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    cell = build_cell(args.arch, SHAPES[args.shape], mesh, opts=opts)
    with jax.set_mesh(mesh):
        hlo = jax.jit(cell.step_fn, donate_argnums=cell.donate).lower(
            *cell.args).compile().as_text()
    profile(hlo, mesh.devices.size, args.top)


if __name__ == "__main__":
    main()
