"""Subprocess helper: elastic rescale must reproduce the static trajectory.

Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
test harness).  Prints machine-checkable lines; exits nonzero on failure.
"""
import sys

import jax
import numpy as np

from repro.checkpoint import snapshot_to_host
from repro.configs import smoke_config
from repro.core.elastic import ElasticTrainer, TrainJobConfig

arch = sys.argv[1] if len(sys.argv) > 1 else "yi-6b"

cfg = smoke_config(arch)
job = TrainJobConfig(global_batch=8, seq_len=32, total_steps=12, seed=3)
devs = jax.devices()
assert len(devs) == 8, len(devs)

static = ElasticTrainer(cfg, job, devs[:4])
for _ in range(12):
    m_static = static.step()

elastic = ElasticTrainer(cfg, job, devs[:4])
for _ in range(4):
    elastic.step()
t1 = elastic.rescale(devs[:2], via_host=True)       # shrink (forced host path)
for _ in range(4):
    elastic.step()
t2 = elastic.rescale(devs[:8])                      # expand (auto -> p2p)
for _ in range(4):
    m_elastic = elastic.step()
t3 = elastic.rescale(devs[:4])                      # revisit: warm mesh cache

pa = jax.tree.leaves(jax.device_get(static.params))
pb = jax.tree.leaves(jax.device_get(elastic.params))
perr = max(float(np.max(np.abs(a.astype(np.float32) - b.astype(np.float32))))
           for a, b in zip(pa, pb))
la = [x["loss"] for x in static.metrics_log]
lb = [x["loss"] for x in elastic.metrics_log]
lerr = max(abs(a - b) for a, b in zip(la, lb))

# the fused snapshot packs a multi-device (replicated) state on one device
fused = snapshot_to_host(elastic.params, fused=True)
plain = snapshot_to_host(elastic.params)
assert all(fused[k].tobytes() == plain[k].tobytes() for k in plain)

print(f"PARAM_ERR {perr:.3e}")
print(f"LOSS_ERR {lerr:.3e}")
print(f"LOSS_FIRST {la[0]:.4f} LOSS_LAST {la[-1]:.4f}")
print(f"STAGES1 {t1.as_dict()}")
print(f"STAGES2 {t2.as_dict()}")
print(f"STAGES3 {t3.as_dict()}")
assert perr < 5e-5, perr
assert lerr < 5e-5, lerr
assert la[-1] < la[0], "loss did not decrease"
assert all(v >= 0 for v in t1.as_dict().values())
assert t1.restart > 0, "restart (re-jit) must be nonzero"
assert t1.path == "host" and t2.path == "p2p", (t1.path, t2.path)
assert t2.checkpoint == 0.0, "p2p path must skip the host snapshot"
# devs[:4] was compiled at startup: the revisit must hit the mesh cache and
# skip the re-jit entirely (warm restart)
assert t3.restart < 0.5 * t2.restart, (t3.restart, t2.restart)
print("OK")
