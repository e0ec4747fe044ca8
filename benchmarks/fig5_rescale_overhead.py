"""Paper Fig. 5 — rescale overhead decomposed into the four stages
(load-balance / checkpoint / restart / restore).

(a) REAL measurements: ElasticTrainer shrink/expand on virtual devices
    (subprocess on 8 CPU devices, rows labelled ``device=cpu:8``) across
    replica counts and model sizes — the JAX analog of the paper's Jacobi
    runs, including the paper's headline findings (restart dominates small
    problems; in-memory ckpt/restore cheap).  Then the fused Pallas pack vs.
    per-leaf snapshot, in this process on the device it holds.
(b) The calibrated analytic model the simulator uses (paper shapes 5a/5b/5c).
(c) Per-phase makespan decomposition of traced simulator runs — where the
    overhead of (a)/(b) actually lands in end-to-end completion time — with
    a reconciliation PASS/FAIL row: the phase sums must match the
    priority-weighted mean completion to <0.1% (same invariant the trace
    auditor enforces).

``run(sim_only=True)`` (the harness ``--fast`` path / CI) skips section (a)
and keeps (b) and (c).
"""
import time

from benchmarks.common import CPU8, emit, kv, phases_kv, run_cpu_helper

HELPER = r"""
import json
import jax
from repro.configs import smoke_config
from repro.core.elastic import ElasticTrainer, TrainJobConfig

devs = jax.devices()
out = []
for arch, width in [("yi-6b", 64), ("yi-6b", 128)]:
    cfg = smoke_config(arch).with_(d_model=width, expected_params=0.0)
    for r0, r1 in [(4, 2), (2, 4), (8, 4), (4, 8)]:
        tr = ElasticTrainer(cfg, TrainJobConfig(global_batch=8, seq_len=32,
                                                total_steps=4, seed=0),
                            devs[:r0])
        tr.step()
        t = tr.rescale(devs[:r1], via_host=True)      # legacy host path
        out.append(dict(width=width, r0=r0, r1=r1, path="host",
                        **t.as_dict()))
        tr.rescale(devs[:r0], via_host=True)          # back; r1 now warm
        t = tr.rescale(devs[:r1])                     # fast: auto p2p + warm
        out.append(dict(width=width, r0=r0, r1=r1, path=t.path,
                        **t.as_dict()))
print("JSON" + json.dumps(out))
"""


def _live_rows():
    for r in run_cpu_helper(HELPER, timeout=1800):
        kind = "shrink" if r["r1"] < r["r0"] else "expand"
        name = (f"fig5.live.{kind}.w{r['width']}.{r['r0']}to{r['r1']}"
                f".{r['path']}")
        emit(name, r["total"] * 1e6,
             f"lb={r['load_balance']:.3f};ckpt={r['checkpoint']:.3f};"
             f"restart={r['restart']:.3f};restore={r['restore']:.3f};{CPU8}")


def _kernel_rows():
    """Slow-lane fig5 kernel section: fused Pallas pack vs. per-leaf
    device_get for the device->host snapshot, in this process.  On a CPU
    the kernel runs in interpret mode (Python speed, validation only); the
    ratio means something on a TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.reshard import snapshot_to_host

    dev = jax.devices()[0]
    label = f"device={dev.platform}:{dev.device_kind}"
    rng = np.random.default_rng(0)
    for n_leaves, leaf_elems in [(16, 4096), (64, 4096), (64, 65536)]:
        tree = {f"layer{i:02d}": {"w": jnp.asarray(
            rng.standard_normal(leaf_elems).astype(np.float32))}
            for i in range(n_leaves)}
        secs = {}
        for name, fused in [("perleaf", False), ("packed", True)]:
            snapshot_to_host(tree, fused=fused)        # warm (trace/compile)
            t0 = time.perf_counter()
            reps = 3
            for _ in range(reps):
                snapshot_to_host(tree, fused=fused)
            secs[name] = (time.perf_counter() - t0) / reps
            emit(f"fig5.kernel.snapshot.{name}.l{n_leaves}x{leaf_elems}",
                 secs[name] * 1e6,
                 f"leaves={n_leaves};elems={leaf_elems};{label}")
        emit(f"fig5.kernel.pack_speedup.l{n_leaves}x{leaf_elems}", 0.0,
             kv(f"{secs['perleaf'] / secs['packed']:.2f}x",
                perleaf_s=secs["perleaf"], packed_s=secs["packed"]) +
             f";{label}")


def _sim_phase_rows():
    """(c): decompose traced end-to-end runs into the obs phase partition
    and assert the decomposition reconciles with the makespan metric."""
    from repro.core.simulator import make_jacobi_jobs, run_variant
    from repro.obs.critical_path import reconcile
    from repro.obs.trace import Tracer, current_tracer, install

    outer = current_tracer()             # harness --trace file, if any
    for variant in ("elastic", "elastic_preempt"):
        specs = make_jacobi_jobs(seed=7, n_jobs=16, submission_gap=90.0)
        with Tracer() as tr, install(tr):
            m = run_variant(variant, specs, total_slots=64,
                            rescale_gap=180.0)
        if outer.enabled:                # tee so fig5.jsonl stays auditable
            for r in tr.records:
                outer.emit(r["kind"], r["t"],
                           **{k: v for k, v in r.items()
                              if k not in ("kind", "t")})
        emit(f"fig5.sim.{variant}.phases", 0.0, phases_kv(m))
        violations = reconcile(tr.records, rel_tol=1e-3)
        total = sum(m.phase_seconds.values())
        drift = abs(total - m.weighted_mean_completion)
        emit(f"fig5.sim.{variant}.phase_reconcile", 0.0, kv(
            "PASS" if not violations else "FAIL",
            phase_total=total, wmct=m.weighted_mean_completion,
            drift_s=drift, violations=len(violations)))


def run(sim_only: bool = False):
    if not sim_only:
        _live_rows()
        _kernel_rows()

    # analytic model (paper Fig. 5a/5b/5c shapes), fast lane (the default
    # the simulator prices) + legacy (paper-faithful synchronous path), and
    # the gating verdict: fast lane must cut every sweep point >=5x
    from repro.core.perf_model import RescaleModel
    sweeps = ([("shrink_half", f"p{p}", p, p // 2, 2 * 4.0 * 8192 ** 2)
               for p in (4, 8, 16, 32, 64)]            # 5a: shrink p -> p/2
              + [("expand_double", f"p{p}", p, 2 * p, 2 * 4.0 * 8192 ** 2)
                 for p in (4, 8, 16, 32)]              # 5b: expand p -> 2p
              + [("shrink32to16", f"n{n}", 32, 16, 2 * 4.0 * n ** 2)
                 for n in (1024, 4096, 8192, 16384, 23000)])  # 5c: size sweep
    fast, legacy = RescaleModel(), RescaleModel(fast_lane=False)
    worst = None
    for sweep, pt, r0, r1, nbytes in sweeps:
        st = fast.stages(r0, r1, nbytes)
        st_l = legacy.stages(r0, r1, nbytes)
        emit(f"fig5.model.{sweep}.{pt}", sum(st.values()) * 1e6,
             ";".join(f"{k}={v:.3f}" for k, v in st.items()))
        emit(f"fig5.model_legacy.{sweep}.{pt}", sum(st_l.values()) * 1e6,
             ";".join(f"{k}={v:.3f}" for k, v in st_l.items()))
        ratio = sum(st_l.values()) / sum(st.values())
        if worst is None or ratio < worst[0]:
            worst = (ratio, f"{sweep}.{pt}")
    emit("fig5.verdict.fastlane_speedup", 0.0, kv(
        "PASS" if worst[0] >= 5.0 else "FAIL",
        min_ratio=round(worst[0], 2), at=worst[1], points=len(sweeps)))

    _sim_phase_rows()
