import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first initialization) — dry-run only; tests/benches see 1 device.

"""Multi-pod dry-run launcher.

For every (architecture x input shape) cell, lower + compile the step
function on the production mesh (16x16 single-pod AND 2x16x16 multi-pod),
then record memory_analysis / cost_analysis / collective-bytes into a JSON
file per cell (results/dryrun/). Failures here are bugs in the system.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, get_config
from repro.configs.base import shapes_for
from repro.launch.input_specs import build_cell
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import collective_bytes, roofline_terms, useful_flops

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile_metrics(cell, mesh):
    fn = cell.fn
    jitted = (fn if hasattr(fn, "lower") and hasattr(fn, "trace")
              else jax.jit(fn, in_shardings=cell.in_shardings,
                           donate_argnums=cell.donate_argnums))
    with mesh:
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return compiled, ma, float(ca.get("flops", 0.0)), \
        float(ca.get("bytes accessed", 0.0)), coll, hlo


def _lm_scan_correction(arch, shape_name, mesh, router, cfg, variants=()):
    """XLA's HloCostAnalysis counts while-loop (scan) bodies ONCE; probe the
    model UNROLLED at fd+1 and fd+2 layers to recover per-layer costs:
    corrected(L) = probe1 + (L - fd - 1) * (probe2 - probe1)."""
    import dataclasses

    fd = cfg.moe.first_dense if cfg.moe is not None else 0
    out = []
    for L in (fd + 1, fd + 2):
        c = dataclasses.replace(cfg, n_layers=L, scan=False)
        cell = build_cell(arch, shape_name, mesh, router=router,
                          cfg_override=c, variants=variants)
        _, _, fl, by, coll, _ = _compile_metrics(cell, mesh)
        out.append((fl, by, coll["total"]))
    (f1, b1, c1), (f2, b2, c2) = out
    L = cfg.n_layers
    k = L - fd - 1
    return (f1 + k * (f2 - f1), b1 + k * (b2 - b1), c1 + k * (c2 - c1))


def run_cell(arch: str, shape_name: str, mesh_kind: str, router=None,
             keep_hlo: bool = False, probe: bool = True,
             variants: tuple = ()) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": int(n_chips), "router": router or "default",
           "variants": list(variants), "ok": False}
    t0 = time.perf_counter()
    try:
        cell = build_cell(arch, shape_name, mesh, router=router,
                          variants=variants)
        t1 = time.perf_counter()
        compiled, ma, flops, bytes_acc, coll, hlo = _compile_metrics(cell, mesh)
        rec["compile_s"] = round(time.perf_counter() - t1, 2)
        cfg = get_config(arch) if not router else get_config(arch, router=router)
        raw = {"flops": flops, "bytes": bytes_acc, "coll": coll["total"]}
        if cfg.family == "lm" and probe and mesh_kind == "single":
            flops, bytes_acc, coll_total = _lm_scan_correction(
                arch, shape_name, mesh, router, cfg, variants)
            coll = dict(coll, total=coll_total)
            rec["scan_corrected"] = True
        rl = roofline_terms(flops, bytes_acc, coll["total"])
        shape = next(s for s in shapes_for(cfg) if s.name == shape_name)
        mf = useful_flops(arch, shape_name, cell.mode, cfg, shape)
        # jaxlib < 0.5 has no peak stat; args + outputs + temps bounds it
        peak = getattr(ma, "peak_memory_in_bytes", None)
        if peak is None:
            peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes)
        rec.update(
            raw_uncorrected=raw,
            ok=True,
            mode=cell.mode,
            note=cell.note,
            peak_memory_per_device=int(peak),
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            generated_code_bytes=int(ma.generated_code_size_in_bytes),
            flops_per_device=flops,
            bytes_per_device=bytes_acc,
            collectives=coll,
            roofline=rl.to_dict(),
            model_flops_global=mf,
            model_flops_ratio=(mf / (flops * n_chips) if flops else None),
            hlo_instructions=hlo.count("\n"),
        )
        if keep_hlo:
            (RESULTS / f"{arch}__{shape_name}__{mesh_kind}.hlo.txt").write_text(hlo)
    except Exception as e:  # noqa: BLE001 — recorded, not swallowed silently
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.perf_counter() - t0, 2)
    return rec


def save(rec: dict, suffix=""):
    RESULTS.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    (RESULTS / name).write_text(json.dumps(rec, indent=1))
    status = "OK " if rec.get("ok") else "FAIL"
    rl = rec.get("roofline", {})
    print(f"[{status}] {name} lower={rec.get('lower_s')}s "
          f"compile={rec.get('compile_s')}s dominant={rl.get('dominant')} "
          f"peakMB={rec.get('peak_memory_per_device', 0) // 2**20}"
          + ("" if rec.get("ok") else f" err={rec.get('error')}"),
          flush=True)
    return rec.get("ok", False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--router", default=None)
    ap.add_argument("--variants", default="",
                    help="comma-separated: fsdp_gather,moe_ep,packed_a2a,"
                         "escn_sub")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        targets = [(a, s.name) for a in ALL_ARCHS
                   for s in shapes_for(get_config(a))]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        targets = [(args.arch, args.shape)]

    variants = tuple(v for v in args.variants.split(",") if v)
    n_fail = 0
    for arch, shape in targets:
        for mk in meshes:
            suffix = f"__{args.router}" if args.router else ""
            if variants:
                suffix += "__v_" + "_".join(variants)
            out = RESULTS / f"{arch}__{shape}__{mk}{suffix}.json"
            if args.skip_existing and out.exists() \
                    and json.loads(out.read_text()).get("ok"):
                print(f"[skip] {out.name}", flush=True)
                continue
            rec = run_cell(arch, shape, mk, router=args.router,
                           keep_hlo=args.keep_hlo, variants=variants)
            if not save(rec, suffix):
                n_fail += 1
    print(f"done; failures: {n_fail}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
