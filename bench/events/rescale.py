"""Elastic event: move the running job onto the first ``width`` chips.

``ElasticTrainer.rescale`` picks its own path (peer-to-peer where a source
chip survives).  The bits of every leaf of the state are summed before and
after, outside the timed call, so the check can see that the state crossed
intact.
"""
from __future__ import annotations

import time

from bench.harness import program


def fire(run, spec: dict, n: int) -> dict:
    tr = run.trainer
    width = spec["widths"][n % len(spec["widths"])]
    with run.span("bench.readback"):
        before = program.fingerprint(tr.params, tr.opt_state)
        before.block_until_ready()
    with run.span("bench.rescale"):
        start = time.perf_counter()
        t = tr.rescale(run.devices[:width])
        end = time.perf_counter()
    with run.span("bench.readback"):
        after = program.fingerprint(tr.params, tr.opt_state)
        after.block_until_ready()
    return {"kind": "rescale", "width": width, "start": start, "end": end,
            "seconds": end - start, "path": t.path, "timings": t.as_dict(),
            "intact": (before, after)}
