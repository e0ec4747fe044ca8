#!/usr/bin/env python3
"""Bring-up check on TPU: the elastic trainer, the checkpoint lane and the
live operator, driven through their normal entry points at yi-6b's published
widths with random weights.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four chips: elastic rescale
                                      # 1->2->4->2 against a static 2-chip run

Each phase checks its own results and raises on a miss, so any failure exits
non-zero before the last line.  Where JAX finds no TPU the script stops at
phase (a).  One process holds the chip(s) throughout and starts no other.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "yi-6b"
# One chip's share of a yi-6b data-parallel training deployment; every width
# as published.  f32 weights, gradients and AdamW state take 16 B/param: a
# layer is 173M params, the embedding and head 67M at the cut vocabulary.
# Compiled for a described v5e chip at batch 4 x 2048 tokens, the train step
# needs 12.18 GB at 2 layers and 16.26 GB at 3 (memory_analysis), so 2
# layers fit the ~14 GB budget of a 16 GB chip.
CUT = {"vocab_size": 8_000, "num_layers": 2}
SEQ_LEN = 2048
GLOBAL_BATCH = 4
SEED = 0
# the peak rate of published 7B llama-architecture pretraining (LLaMA,
# arXiv:2302.13971); the repo default 3e-3 is sized for smoke widths, and at
# d_model 4096 its first AdamW step throws the loss from 9.4 to 20.9
PEAK_LR = 3e-4

# The first loss sits near ln(vocab): the head is initialised N(0, 1/d_model)
# and the final norm gives unit-RMS hidden states, so logits are ~N(0, 1) and
# the expected first cross-entropy is ln(vocab) + 1/2.
LOSS0_BOUND = 1.0
# The Pallas and XLA attention paths differ only in rounding: both run their
# matmuls at the default precision and differ in the order of the online
# softmax, at most ~2^-8 relative per element.  Output and gradients stay
# within 2% of the largest magnitude of the other path's (or of the f32
# reference's); a wrong mask, head mapping or scale is off by O(1).
FLASH_REL_TOL = 2e-2
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_dkv",
                 "flash_attention_dq")
# Rescaled and static runs differ only in the order of the cross-replica
# sums (f32).
RESCALE_LOSS_TOL = 1e-3
RESCALE_PARAM_REL_TOL = 1e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def lowered_has_kernel(text: str) -> bool:
    return "tpu_custom_call" in text


def job_config(total_steps: int, seed: int = SEED):
    from repro.core.elastic import TrainJobConfig
    return TrainJobConfig(global_batch=GLOBAL_BATCH, seq_len=SEQ_LEN,
                          total_steps=total_steps, seed=seed,
                          peak_lr=PEAK_LR, dtype="float32")


def chip_config():
    from repro.configs import get_config
    published = get_config(ARCH)
    for key, value in CUT.items():
        print(f"config {ARCH}: {key} {getattr(published, key)} -> {value} "
              f"(one chip's share of a data-parallel {ARCH} training "
              f"deployment)")
    cfg = published.with_(expected_params=0.0, **CUT)
    print(f"config {ARCH}: as published d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}, kv_heads {cfg.num_kv_heads}, head_dim "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}; seq_len {SEQ_LEN}, "
          f"global_batch {GLOBAL_BATCH}, float32 state")
    return cfg


def timed_steps(tr, n: int):
    import jax
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(tr.step()["loss"])
        jax.block_until_ready((tr.params, tr.opt_state))
        secs.append(time.perf_counter() - t0)
    check(all(math.isfinite(x) for x in losses), f"finite losses {losses}")
    return losses, secs


def state_bytes(tr) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves((tr.params, tr.opt_state)))


# -- (b) training -------------------------------------------------------------

def phase_train(cfg, job, dev, steps: int = 4):
    from repro.core.elastic import ElasticTrainer
    tr = ElasticTrainer(cfg, job, [dev])
    ma = tr.compiled_step.memory_analysis()
    print(f"(b) trainer built (init + compile) in {tr.startup_time} s; "
          f"state {state_bytes(tr)} B; step memory_analysis: argument "
          f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B")
    losses, secs = timed_steps(tr, steps)
    expected = math.log(cfg.vocab_size)
    check(abs(losses[0] - expected) <= LOSS0_BOUND,
          f"first loss {losses[0]} within {LOSS0_BOUND} of ln(vocab) "
          f"{expected}")
    print(f"(b) losses {losses}")
    print(f"(b) step seconds: warm-up {secs[0]}, then {secs[1:]}")
    print(f"(b) peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")
    text = tr.compiled_step.as_text()
    check(all(name in text for name in FLASH_KERNELS),
          f"train step holds the Pallas attention kernels {FLASH_KERNELS}")
    print("(b) training: PASS")


# -- (c) Pallas attention -----------------------------------------------------

def phase_pallas(cfg, dev):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.blocked import blocked_attention

    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    scale = hd ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, w = (jax.device_put(jax.random.normal(key, (1, SEQ_LEN, H, hd)), dev)
            for key in ks[:2])
    k, v = (jax.device_put(jax.random.normal(key, (1, SEQ_LEN, KV, hd)), dev)
            for key in ks[2:])

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, scale=scale)

    def blocked(q, k, v):
        return blocked_attention(q, k, v, True, scale)

    def grads(att):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(att(q, k, v) * w),
                                (0, 1, 2)))

    text = grads(flash).lower(q, k, v).as_text()
    check(all(name in text for name in FLASH_KERNELS),
          f"flash attention and its gradient lower to {FLASH_KERNELS}")
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(q, k, v, causal=True, scale=scale)
    out = jax.jit(flash)(q, k, v)
    pairs = [("out", "blocked", out, jax.jit(blocked)(q, k, v)),
             ("out", "the f32 reference", out, want)]
    pairs += [(f"d{n}", "blocked", a, b) for n, a, b in
              zip("qkv", grads(flash)(q, k, v), grads(blocked)(q, k, v))]
    for what, against, got, other in pairs:
        err = float(jnp.max(jnp.abs(got - other)))
        mag = float(jnp.max(jnp.abs(other)))
        check(err <= FLASH_REL_TOL * mag,
              f"flash {what} against {against}: max error {err} within "
              f"{FLASH_REL_TOL} x {mag}")
        print(f"(c) flash {what} (1, {SEQ_LEN}, {H}/{KV} heads, {hd}) "
              f"against {against}: max abs error {err}, max magnitude {mag}")
    print("(c) Pallas attention: PASS")


# -- (d) checkpoint lane ------------------------------------------------------

def phase_checkpoint(cfg, job, dev, root: str):
    import jax

    from repro.checkpoint import DiskCheckpointStore, snapshot_to_host
    from repro.core.elastic import ElasticTrainer
    from repro.kernels.pack import pack_leaves_pallas

    store = DiskCheckpointStore(root)
    tr = ElasticTrainer(cfg, job, [dev])
    timed_steps(tr, 1)
    t0 = time.perf_counter()
    tr.save_disk_async(store, "smoke", delta=True)
    submit_s = time.perf_counter() - t0
    (want,), _ = timed_steps(tr, 1)          # overlaps the disk write
    t0 = time.perf_counter()
    tr.ckpt_barrier()
    barrier_s = time.perf_counter() - t0
    print(f"(d) async delta save of {state_bytes(tr)} B of state: snapshot "
          f"{submit_s} s, barrier wait {barrier_s} s after one step, "
          f"{store.last_bytes_written} B written")

    leaves = jax.tree.leaves(tr.params)
    lowered = jax.jit(pack_leaves_pallas).lower(leaves).as_text()
    check(lowered_has_kernel(lowered), "pack lowers to the Pallas kernel")
    snapshot_to_host(tr.params, fused=True)          # compile
    t0 = time.perf_counter()
    fused = snapshot_to_host(tr.params, fused=True)
    fused_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    plain = snapshot_to_host(tr.params)
    plain_s = time.perf_counter() - t0
    check(list(fused) == list(plain) and all(
        fused[k].dtype == plain[k].dtype and fused[k].shape == plain[k].shape
        and fused[k].tobytes() == plain[k].tobytes() for k in plain),
        "fused snapshot equals the per-leaf snapshot bit for bit")
    nbytes = sum(a.nbytes for a in plain.values())
    print(f"(d) snapshot of {nbytes} B of parameters: fused pack {fused_s} "
          f"s, per-leaf {plain_s} s, bit-equal")
    tr.close()
    del tr, leaves, fused, plain
    gc.collect()

    tr = ElasticTrainer(cfg, job, [dev])
    step = tr.restore_disk(store, "smoke")
    (got,), _ = timed_steps(tr, 1)
    check(got == want, f"resumed loss {got} equals uninterrupted {want}")
    print(f"(d) restored step {step} into a fresh trainer: loss {got}, "
          f"uninterrupted {want}, equal")
    print("(d) checkpoint lane: PASS")


# -- (e) live operator --------------------------------------------------------

def phase_operator(cfg, job, devs):
    import dataclasses

    from repro.core import (ElasticClusterController, ElasticTrainer,
                            JobSpec, JobStatus, PolicyConfig)

    logs = {}

    def factory(job_id, seed):
        def make(devices):
            tr = ElasticTrainer(cfg, dataclasses.replace(
                job, total_steps=2, seed=seed), devices)
            logs[job_id] = tr.metrics_log
            return tr
        return make

    op = ElasticClusterController(devs, slots=1,
                                  policy=PolicyConfig(rescale_gap=0.0))
    for i, job_id in enumerate(("job-a", "job-b")):
        op.submit(JobSpec(job_id, 1, 1, 1, 0.0), factory(job_id, SEED + i))
    t0 = time.perf_counter()
    op.run()
    wall = time.perf_counter() - t0
    jobs = op.cluster.jobs
    check(sorted(jobs) == ["job-a", "job-b"] and all(
        j.status == JobStatus.COMPLETED for j in jobs.values()),
        "both jobs completed")
    check(all(live.trainer is None for live in op.live.values()),
          "completed jobs hold no trainer")
    for job_id, log in sorted(logs.items()):
        losses = [m["loss"] for m in log]
        check(len(losses) == 2 and all(map(math.isfinite, losses)),
              f"{job_id} ran 2 finite steps: {losses}")
        print(f"(e) {job_id}: losses {losses}")
    gc.collect()
    print(f"(e) controller ran 2 jobs on 1 slot in {wall} s; "
          f"bytes_in_use after {devs[0].memory_stats()['bytes_in_use']}, "
          f"peak_bytes_in_use {devs[0].memory_stats()['peak_bytes_in_use']}")
    print("(e) live operator: PASS")


# -- four chips: elastic rescale ----------------------------------------------

def phase_rescale(cfg, job, devs):
    import jax
    import numpy as np

    from repro.checkpoint import snapshot_to_host
    from repro.core.elastic import ElasticTrainer
    from repro.kernels.pack import pack_leaves_pallas

    plan = [("host", 2), ("host", 4), ("host", 2), ("auto", 4), ("auto", 2)]
    steps = len(plan) + 1

    static = ElasticTrainer(cfg, job, devs[:2])
    want, _ = timed_steps(static, steps)
    want_params = jax.device_get(static.params)
    del static
    gc.collect()

    tr = ElasticTrainer(cfg, job, devs[:1])
    got, secs = timed_steps(tr, 1)
    for how, n in plan:
        t = tr.rescale(devs[:n], via_host=True if how == "host" else None)
        expect = "host" if how == "host" else "p2p"
        check(t.path == expect, f"rescale to {n} took path {t.path}")
        print(f"(r) rescale to {n} chips via {t.path}: {t.as_dict()}")
        loss, sec = timed_steps(tr, 1)
        got += loss
        secs += sec
    cold = min(t.restart for t in tr.rescale_log[:2])
    check(all(t.restart < 0.5 * cold for t in tr.rescale_log[2:]),
          "revisited meshes skip the re-jit")
    diffs = [abs(a - b) for a, b in zip(got, want)]
    check(max(diffs) <= RESCALE_LOSS_TOL,
          f"rescaled losses {got} match static {want}")
    got_params = jax.device_get(tr.params)
    num = sum(float(np.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(got_params), jax.tree.leaves(want_params)))
    den = sum(float(np.sum(b ** 2)) for b in jax.tree.leaves(want_params))
    rel = math.sqrt(num / den)
    check(rel <= RESCALE_PARAM_REL_TOL,
          f"parameter relative difference {rel}")
    print(f"(r) losses rescaled {got}")
    print(f"(r) losses static 2-chip {want}")
    print(f"(r) max loss difference {max(diffs)}, parameter relative "
          f"difference {rel}; step seconds {secs}")

    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    check(all(p >= state_bytes(tr) for p in peaks),
          f"every chip held a full replica: peaks {peaks}")
    print(f"(r) per-device peak_bytes_in_use {peaks} "
          f"(state {state_bytes(tr)} B)")

    # A Mosaic kernel is not partitioned by XLA (a pallas_call over the
    # 4-chip replicated state is refused), so fused=True does not spread
    # over the chips: it packs one replica on one chip.
    tr.rescale(devs)
    check(all(x.sharding.is_fully_replicated and
              len(x.sharding.device_set) == len(devs)
              for x in jax.tree.leaves(tr.params)),
          f"state replicated over {len(devs)} chips")
    first = [x.addressable_shards[0].data for x in jax.tree.leaves(tr.params)]
    packed = pack_leaves_pallas(first)
    t0 = time.perf_counter()
    fused = snapshot_to_host(tr.params, fused=True)
    fused_s = time.perf_counter() - t0
    plain = snapshot_to_host(tr.params)
    check(list(fused) == list(plain) and all(
        fused[k].tobytes() == plain[k].tobytes() for k in plain),
        f"fused snapshot equals per-leaf on {len(devs)} chips")
    print(f"(r) fused pack on {len(devs)} chips: not partitioned; packs one "
          f"replica on devices {sorted(d.id for d in packed.devices())} in "
          f"{fused_s} s (compile included); bit-equal to per-leaf")
    print("(r) elastic rescale: PASS")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax                                  # (a) before anything else
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"(a) no TPU: JAX found {dev.platform} devices")
    if len(devs) < args.chips:
        sys.exit(f"(a) {args.chips} chips asked, {len(devs)} found")
    print(f"(a) device: {dev.platform} {dev.device_kind} x {len(devs)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    cfg = chip_config()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_rescale(cfg, job_config(total_steps=8), devs[:4])
    else:
        phase_train(cfg, job_config(total_steps=8), dev)
        gc.collect()
        phase_pallas(cfg, dev)
        gc.collect()
        root = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            phase_checkpoint(cfg, job_config(total_steps=8), dev, root)
        finally:
            shutil.rmtree(root, ignore_errors=True)
        gc.collect()
        phase_operator(cfg, job_config(total_steps=2), devs[:1])
    print(f"all phases passed in {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
