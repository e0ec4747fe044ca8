#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload yi6b.train --seed 7 --seconds 40 --trace 0

The cell, its configuration, traffic, metrics and limits are found by name
from ``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the per-layer metrics, read from a
profiler trace of the window.  The last line of standard output is one JSON
object; the last lines of standard error give each number compared beside
its limit.  Where JAX finds no TPU, or fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# fixed, inside the checkout: the path is part of the compile cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench.harness import device, spec
    cell = spec.resolve(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = device.require_chips(cell.chips)
    except device.NoChip as e:
        sys.exit(str(e))

    from bench.harness import driver
    result = driver.run_cell(cell, args.seed, args.seconds, args.trace == 1,
                             devs[:cell.chips], T_START,
                             device.peaks(devs[0].device_kind))
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
