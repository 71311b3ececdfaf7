"""Serve a model with batched requests: prefill a batch of prompts, then
greedy-decode continuations through the KV/SSM cache.

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-1.3b --smoke \
        --prompt-len 64 --gen 32
"""
import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_smoke
from repro.models import decode_step, init_params, prefill
from repro.obs.timer import now


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    rng = jax.random.PRNGKey(0)
    params = init_params(cfg, rng)
    batch = {"tokens": jax.random.randint(rng, (args.batch, args.prompt_len),
                                          0, cfg.vocab)}
    if cfg.n_vision_tokens:
        batch["vision"] = 0.02 * jax.random.normal(
            rng, (args.batch, cfg.n_vision_tokens, cfg.d_model))

    t0 = now()
    logits, cache, pos = prefill(cfg, params, batch)
    logits.block_until_ready()
    t_prefill = now() - t0

    step = jax.jit(lambda t, c, p: decode_step(cfg, params, t, c, p))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    outs = [tok]
    t0 = now()
    for _ in range(args.gen - 1):
        logits, cache, pos = step(tok, cache, pos)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    jax.block_until_ready(outs[-1])
    t_decode = now() - t0

    gen = jnp.concatenate(outs, axis=1)
    print(f"{cfg.name}: prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill*1e3:.0f} ms; decoded {args.gen} tokens/request in "
          f"{t_decode*1e3:.0f} ms "
          f"({args.batch*args.gen/max(t_decode,1e-9):.0f} tok/s)")
    print("first request's continuation ids:", gen[0].tolist())


if __name__ == "__main__":
    main()
