#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/tools/readings.py --workload yi6b.train --seeds 1,2,3 \
        --out chiprun_out/readings.jsonl

For each seed it reads the cell's numbers for:

- ``program``: a sound run of the program, primed exactly as a benchmark run
  primes it (one trainer, re-primed for each seed);
- ``control``: the reference put in the program's place in bfloat16, the
  precision below the configuration's float32;
- ``half_batch``: the reference in the program's place with half of the
  batch left out and the mean taken over the rest;
- ``no_exchange`` (cells whose set-up runs on several chips): each step's
  gradient from the first replica's rows alone, as if the exchange between
  chips were left out.

A step that returns its state unchanged reads 1 in ``change_gap`` by its
definition and needs no run.  ``--no-program`` skips the program, so the
control and faults of a four-chip cell can be read on one chip (its sound
readings are then those of its benchmark runs).  The benchmark's own runs
never run this.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def reset(run, cell) -> None:
    """Back to the set-up's first width, with fresh optimizer state."""
    import jax
    import jax.numpy as jnp
    tr = run.trainer
    first = cell.traffic["setup_widths"][0]
    if len(tr.devices) != first:
        tr.rescale(run.devices[:first])
    tr.opt_state = jax.tree.map(jnp.zeros_like, tr.opt_state)
    tr.step_idx = 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--variants", help="comma-separated; default all")
    ap.add_argument("--no-program", action="store_true",
                    help="read the control and faults only (one chip)")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from bench.harness import check, device, driver, reference, spec
    from bench.run import CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.resolve(args.workload)

    progs = {}
    if not args.no_program:
        devs = device.require_chips(cell.chips)[:cell.chips]
        run = driver.start(cell, devs)
        for seed in seeds:
            reset(run, cell)
            prog, crossings = driver.prime(run, cell, seed)
            prog["state_mismatch"] = driver.mismatches(crossings)
            progs[seed] = prog
        del run
        gc.collect()

    widths = cell.traffic["setup_widths"]
    variants = {
        "control": dict(dtype=jnp.bfloat16, precision=None),
        "half_batch": dict(rows_of_step=lambda s, B: range(B // 2)),
    }
    if len(set(widths[:driver.CHECKED_STEPS])) > 1 or widths[0] > 1:
        variants["no_exchange"] = dict(
            rows_of_step=lambda s, B: range(B // widths[s]))
    if args.variants:
        variants = {k: variants[k] for k in args.variants.split(",")}
    with open(args.out, "a") as f:
        for seed in seeds:
            ref = reference.run(cell.model, cell.config, cell.traffic, seed)
            row = {"seed": seed, "workload": cell.name}
            if seed in progs:
                row["program"] = check.numbers(progs[seed], ref)
                row["state_mismatch"] = progs[seed]["state_mismatch"]
            for name, kw in variants.items():
                out = reference.run(cell.model, cell.config, cell.traffic,
                                    seed, **kw)
                row[name] = check.numbers(out, ref)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
