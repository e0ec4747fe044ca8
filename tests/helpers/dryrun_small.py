"""Subprocess helper: the dry-run machinery on a small (2,4) mesh with reduced
configs — lower + compile + memory/cost/collective extraction end-to-end."""
import sys

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import smoke_config
from repro.launch.cells import make_cell
from repro.utils.hlo import collective_bytes
from repro.utils.roofline import V5E_KIND, peaks, roofline_from_analysis

devs = jax.devices()
assert len(devs) == 8, len(devs)
mesh = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))

for arch, shape in [("yi-6b", "train_4k"), ("granite-moe-3b-a800m", "train_4k"),
                    ("mamba2-1.3b", "decode_32k"),
                    ("jamba-v0.1-52b", "long_500k")]:
    cfg = smoke_config(arch)
    # shrink the shape to CPU scale by overriding via the SHAPES entry
    from repro.configs.base import ShapeConfig, SHAPES
    s = SHAPES[shape]
    small = ShapeConfig(s.name, 64 if s.kind != "train" else 32, 8, s.kind)
    import repro.launch.cells as cells
    orig = dict(cells.SHAPES)
    cells.SHAPES = dict(cells.SHAPES)
    cells.SHAPES[shape] = small
    try:
        cell = make_cell(arch, shape, mesh, cfg_override=cfg)
        lowered = cell.lower()
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        terms = roofline_from_analysis(ca, coll.get("total", 0),
                                       cell.model_flops, 8, peaks(V5E_KIND))
        assert ma.temp_size_in_bytes >= 0
        assert ca.get("flops", 0) > 0
        assert terms.bottleneck in ("compute", "memory", "collective")
        print(f"{arch}|{shape}: flops/dev={ca.get('flops', 0):.3g} "
              f"coll={coll.get('total', 0)} bottleneck={terms.bottleneck}")
    finally:
        cells.SHAPES = orig
print("OK")
