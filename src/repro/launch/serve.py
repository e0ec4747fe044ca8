"""Batched serving driver: prefill a batch of prompts, then decode N tokens
synchronously (greedy).  Works on any --arch (use --smoke on CPU).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --batch 4 --prompt-len 16 --gen 16
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import time

    import jax
    import jax.numpy as jnp

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_config, smoke_config
    from repro.models import (decode_step, init_params, pad_cache, prefill)

    cfg = (smoke_config(args.arch) if args.smoke
           else get_config(args.arch)).with_(dtype="float32")
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    B, S0 = args.batch, args.prompt_len
    max_len = S0 + args.gen
    prompts = jax.random.randint(key, (B, S0), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.enc_layers:
        batch["enc_embeds"] = jax.random.normal(
            key, (B, S0, cfg.d_model), jnp.float32)

    t0 = time.perf_counter()
    cache, logits = prefill(cfg, params, batch)
    cache = pad_cache(cfg, cache, S0, max_len)
    t_prefill = time.perf_counter() - t0
    print(f"[serve] prefill {B}x{S0}: {t_prefill:.3f}s "
          f"({B * S0 / t_prefill:.0f} tok/s)")

    dstep = jax.jit(lambda c, t, p: decode_step(cfg, params, c, t, p))
    toks = jnp.argmax(logits, -1)[:, None]
    out = [toks]
    t0 = time.perf_counter()
    for t in range(S0, max_len - 1):
        logits, cache = dstep(cache, toks, jnp.int32(t))
        toks = jnp.argmax(logits, -1)[:, None]
        out.append(toks)
    jax.block_until_ready(toks)
    t_dec = time.perf_counter() - t0
    n = len(out) - 1
    print(f"[serve] decoded {n} steps x {B} seqs: {t_dec:.3f}s "
          f"({B * n / max(t_dec, 1e-9):.0f} tok/s)")
    gen = jnp.concatenate(out, axis=1)
    print("[serve] sample generations (token ids):")
    for b in range(min(B, 4)):
        print("  ", gen[b, :12].tolist())


if __name__ == "__main__":
    main()
