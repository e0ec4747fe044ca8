"""End-to-end training driver.

Runs a real training job for any ``--arch`` on the local device(s), with the
elastic runtime underneath: the job can be rescaled on the fly (via
``--rescale-at step:replicas``), checkpoints to disk for fault tolerance, and
resumes with ``--restart``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 50 --global-batch 8 --seq-len 64
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b --smoke \
      --steps 20 --rescale-at 10:2
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--devices", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--virtual-devices", type=int, default=0,
                    help="force N virtual host devices (set before jax init)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--rescale-at", action="append", default=[],
                    help="step:new_replica_count (repeatable)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--restart", action="store_true",
                    help="resume from the latest disk checkpoint")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.virtual_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.virtual_devices}")

    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.checkpoint import DiskCheckpointStore
    from repro.configs import get_config, smoke_config
    from repro.core.elastic import ElasticTrainer, TrainJobConfig

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    devices = jax.devices()
    if args.devices:
        devices = devices[:args.devices]

    job = TrainJobConfig(global_batch=args.global_batch, seq_len=args.seq_len,
                         total_steps=args.steps, seed=args.seed,
                         peak_lr=args.lr, dtype=args.dtype)
    trainer = ElasticTrainer(cfg, job, devices)
    print(f"[train] arch={cfg.name} params={sum(x.size for x in jax.tree.leaves(trainer.params)):,} "
          f"replicas={trainer.replicas} startup={trainer.startup_time:.2f}s")

    store = None
    if args.checkpoint_dir:
        store = DiskCheckpointStore(args.checkpoint_dir)
        if args.restart:
            try:
                step = trainer.restore_disk(store, cfg.name)
                print(f"[train] restarted from disk checkpoint at step {step}")
            except FileNotFoundError:
                print("[train] no checkpoint found; starting fresh")

    rescales = {}
    for spec in args.rescale_at:
        s, r = spec.split(":")
        rescales[int(s)] = int(r)

    while not trainer.done:
        if trainer.step_idx in rescales:
            new_r = rescales[trainer.step_idx]
            t = trainer.rescale(devices[:new_r])
            print(f"[train] rescale -> {new_r} replicas: "
                  + " ".join(f"{k}={v:.3f}s" for k, v in t.as_dict().items()))
        m = trainer.step()
        if trainer.step_idx % args.log_every == 0 or trainer.done:
            print(f"[train] step {m['step']:5d} loss={m['loss']:.4f} "
                  f"grad_norm={m['grad_norm']:.3f} replicas={m['replicas']}")
        if store and args.checkpoint_every and \
                trainer.step_idx % args.checkpoint_every == 0:
            dt = trainer.save_disk(store, cfg.name)
            print(f"[train] disk checkpoint @ step {trainer.step_idx} "
                  f"({dt:.2f}s)")

    losses = [m["loss"] for m in trainer.metrics_log]
    print(f"[train] done. loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
