"""Cells at a size a CPU test run can hold, built from the real files.

The sizes are cut and the limits are the tests' own; everything else (the
traffic, the optimizer, the model modules, the harness, the metrics of the
cell that runs the same traffic) is what the chip runs.
"""
from __future__ import annotations

import copy
import os

from bench.harness.spec import BENCH, load_json, resolve

TINY = {
    "yi6b": {"num_layers": 2, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
             "vocab_size": 128},
    "mamba2": {"num_layers": 2, "d_model": 64, "vocab_size": 128,
               "ssm": {"d_state": 16, "head_dim": 16, "expand": 2,
                       "num_groups": 1, "conv_width": 4, "chunk": 8}},
}
# at these sizes on the CPU the program and the reference agree to f32
# rounding; each fault the tests plant moves one of the numbers far more
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-2}


def cell(name: str, seq_len: int = 32):
    """``<config>.<traffic>``, from ``bench/configs`` and ``bench/traffic``;
    the metrics are those of ``yi6b.train`` in BENCHMARK.json."""
    config_name, traffic = name.split(".")
    c = copy.deepcopy(resolve("yi6b.train"))
    c.name = name
    c.config = load_json(os.path.join(BENCH, "configs", config_name + ".json"))
    c.config.update(copy.deepcopy(TINY[config_name]))
    c.traffic = load_json(os.path.join(BENCH, "traffic", traffic + ".json"))
    c.traffic["seq_len"] = seq_len
    c.chips = max(c.traffic["setup_widths"])
    c.limits = dict(LIMITS)
    if c.traffic["events"]:
        c.limits["state_mismatch"] = 0
    return c
