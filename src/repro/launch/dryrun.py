import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline inputs.  (The XLA_FLAGS line above MUST
precede any jax import — jax locks the device count at first init.)

Per cell:
  1. full-model lower+compile on the requested mesh (layer stacks as rolled
     ``lax.scan``): proves the sharding config is coherent and yields
     ``compiled.memory_analysis()`` (per-device bytes: fits / doesn't fit).
  2. collective schedule: parsed from the compiled (post-SPMD) HLO
     (utils/hlo.py).  Collectives inside while bodies are counted once by the
     text parse, so ops in loop-like computations are multiplied by the layer
     trip count (the layer scan is the dominant loop; nested scans hold no
     collectives by construction — mixer-internal tensors are resharded
     OUTSIDE the inner scans).
  3. FLOPs / HBM traffic: analytic models (utils/flops.py).  XLA's
     cost_analysis counts while bodies ONCE regardless of trips (verified —
     a 10-step scanned matmul reports the flops of one), so compiled counts
     cannot cost scan-structured models; the compiled aggregate is still
     recorded as ``xla_cost`` for reference.

Results accumulate in a JSON file (default results/dryrun.json), resumable
via --skip-existing; EXPERIMENTS.md tables are generated from it.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-also] [--skip-existing]
"""
import argparse
import json
import time
import traceback


def _cell_key(arch: str, shape: str, mesh_name: str, rules: str = "") -> str:
    return f"{arch}|{shape}|{mesh_name}" + (f"|{rules}" if rules else "")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             rules_name=None, rule_overrides=None) -> dict:
    import jax
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.launch.cells import make_cell, train_rules_name, \
        decode_rules_name
    from repro.launch.mesh import chips_in, make_production_mesh
    from repro.utils.flops import cell_flops, cell_hbm_bytes
    from repro.utils.hlo import collective_bytes
    from repro.utils.roofline import V5E_KIND, peaks, roofline_from_analysis

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    chips = chips_in(mesh)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    eff_rules = rules_name or (train_rules_name(arch) if shape.kind == "train"
                               else decode_rules_name(arch, shape))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": chips, "rules": eff_rules, "status": "ok"}

    t0 = time.time()
    cell = make_cell(arch, shape_name, mesh, rules_name=rules_name,
                     rule_overrides=rule_overrides)
    lowered = cell.lower()
    rec["lower_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
    }
    rec["fits_hbm"] = rec["memory"]["peak_bytes"] <= 16e9
    ca = compiled.cost_analysis() or {}
    rec["xla_cost"] = {"flops": ca.get("flops", 0.0),
                       "bytes": ca.get("bytes accessed", 0.0)}

    # collective schedule: per-device bytes; loop-like computations x layers
    n_blocks = cell.scan_trips["while"]
    hlo = compiled.as_text()
    rec["collectives_once"] = collective_bytes(hlo)
    rec["collectives"] = collective_bytes(
        hlo, body_multipliers={"while": n_blocks, "body": n_blocks,
                               "region": 1})
    del compiled, lowered

    flops_global = cell_flops(cell.cfg, shape)
    hbm_global = cell_hbm_bytes(cell.cfg, shape)
    terms = roofline_from_analysis(
        {"flops": flops_global / chips, "bytes accessed": hbm_global / chips},
        rec["collectives"].get("total", 0.0),
        cell.model_flops, chips, peaks(V5E_KIND))
    rec["model_flops"] = cell.model_flops
    rec["analytic"] = {"flops_global": flops_global,
                       "hbm_bytes_global": hbm_global}
    rec["roofline"] = terms.as_dict()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--rules", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--multi-pod-also", action="store_true",
                    help="run each cell on both meshes")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

    from repro.launch.cells import all_cells

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    if args.all:
        targets = [(a, s) for a, s, ok, _ in all_cells() if ok]
    else:
        targets = [(args.arch, args.shape)]
    meshes = [args.multi_pod] if not args.multi_pod_also else [False, True]

    for a, s, ok, why in all_cells():
        if not ok:
            results[_cell_key(a, s, "skipped")] = {
                "arch": a, "shape": s, "status": "skipped", "reason": why}

    for arch, shape in targets:
        for mp in meshes:
            mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
            key = _cell_key(arch, shape, mesh_name, args.rules or "")
            if args.skip_existing and results.get(key, {}).get("status") == "ok":
                print(f"[skip] {key}", flush=True)
                continue
            print(f"[run ] {key}", flush=True)
            t0 = time.time()
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               rules_name=args.rules)
            except Exception as e:
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {key}: {e!r}", flush=True)
            rec["wall_s"] = round(time.time() - t0, 1)
            results[key] = rec
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1, sort_keys=True)
            if rec.get("status") == "ok":
                mem = rec.get("memory", {})
                rl = rec.get("roofline", {})
                print(f"   ok mem={mem.get('peak_bytes', 0)/1e9:.2f}GB/chip "
                      f"fits={rec.get('fits_hbm')} "
                      f"bottleneck={rl.get('bottleneck', '?')} "
                      f"useful={rl.get('useful_flops_fraction', 0):.2f} "
                      f"mfu_bound={rl.get('mfu_bound', 0):.3f} "
                      f"({rec['wall_s']}s)", flush=True)


if __name__ == "__main__":
    main()
