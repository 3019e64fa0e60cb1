"""Batched serving driver: prefill a batch of prompts, decode greedily.

Demonstrates the serving path (prefill_step/decode_step with KV/SSM caches)
end-to-end on any arch; CPU-friendly with ``--reduced``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import configs
from ..data import synth
from ..models import registry
from ..train import steps
from .cache import init_compile_cache
from .mesh import make_local_mesh


def main() -> None:
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    if cfg.family == "audio":
        raise SystemExit("use an LM-family arch for serve (enc-dec decode "
                         "is exercised in tests)")
    mesh = make_local_mesh()
    params = registry.init(cfg, jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.gen_tokens

    toks = synth.lm_tokens(args.seed, args.batch * args.prompt_len + 1,
                           cfg.vocab_size)
    prompts = toks[:args.batch * args.prompt_len].reshape(
        args.batch, args.prompt_len)
    batch = {"tokens": jnp.asarray(prompts)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.zeros(
            (args.batch, 4, cfg.d_model), jnp.bfloat16)
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(args.prompt_len, dtype=jnp.int32),
            (3, args.batch, args.prompt_len))

    with mesh:
        prefill = jax.jit(lambda p, b: steps.prefill_step(
            cfg, p, b, max_len=max_len))
        decode = jax.jit(lambda p, t, c: steps.decode_step(cfg, p, t, c),
                         donate_argnums=(2,))

        t0 = time.perf_counter()
        logits, cache = prefill(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        out = [jnp.argmax(logits, -1)[:, None]]
        t0 = time.perf_counter()
        for _ in range(args.gen_tokens - 1):
            logits, cache = decode(params, out[-1].astype(jnp.int32), cache)
            out.append(jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
        jax.block_until_ready(out[-1])
        t_decode = time.perf_counter() - t0

    gen = np.asarray(jnp.concatenate(out, 1))
    tok_s = args.batch * (args.gen_tokens - 1) / max(t_decode, 1e-9)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen_tokens}")
    print(f"prefill {t_prefill*1e3:.1f} ms; decode {t_decode*1e3:.1f} ms "
          f"({tok_s:.1f} tok/s) on {jax.devices()[0].device_kind}")
    print("first sequence:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
