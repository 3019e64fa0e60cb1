"""Production training driver.

Fault-tolerance model (DESIGN.md §2): training is segmented; every segment
boundary asynchronously materializes TrainState into the content-addressed
store. A restarted job (``--resume``) restores the newest checkpoint —
re-sharded onto whatever mesh the new job has (elastic) — and the
deterministic batcher (pure function of (seed, step)) replays the exact
data stream. A per-step watchdog flags stragglers via z-score on step time.

CPU-friendly: ``--reduced`` runs the same code path on the smoke config.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import configs
from ..checkpoint import CheckpointManager
from ..core.store import Store
from ..data import synth
from ..data.pipeline import TokenBatcher
from ..train import steps
from .cache import init_compile_cache
from .mesh import make_local_mesh, make_production_mesh
from .shapes import train_shardings


class Watchdog:
    """Straggler/step-time anomaly detection."""

    def __init__(self, z_thresh: float = 4.0):
        self.times: list[float] = []
        self.z = z_thresh

    def observe(self, dt: float) -> str | None:
        self.times.append(dt)
        if len(self.times) < 10:
            return None
        mu = float(np.mean(self.times[-50:-1]))
        sd = float(np.std(self.times[-50:-1])) + 1e-9
        if (dt - mu) / sd > self.z:
            return (f"straggler suspected: step took {dt:.3f}s "
                    f"(mean {mu:.3f}s, z={(dt - mu) / sd:.1f})")
        return None


def main() -> None:
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="helix100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--segment-steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default="results/train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} devices={len(jax.devices())} "
          f"device={jax.devices()[0].device_kind}")

    tokens = synth.lm_tokens(args.seed, max(2_000_000,
                                            args.batch * (args.seq + 1) * 4),
                             cfg.vocab_size)
    batcher = TokenBatcher(tokens, args.batch, args.seq, seed=args.seed)

    store = Store(f"{args.workdir}/store")
    ckpt = CheckpointManager(store, run_name=f"{cfg.name}-s{args.seed}")

    state_shard, _ = train_shardings(cfg, mesh, "train_2d")

    with mesh:
        start_step = 0
        if args.resume:
            latest = ckpt.latest_step()
            if latest is not None:
                state = jax.device_put(ckpt.restore(latest), state_shard)
                start_step = latest
                print(f"resumed from step {latest} "
                      f"(elastic restore onto {dict(mesh.shape)})")
        if start_step == 0:
            state = steps.init_train_state(cfg, jax.random.PRNGKey(args.seed))
            state = jax.device_put(state, state_shard)

        jstep = jax.jit(
            lambda st, b: steps.train_step(
                cfg, st, b, peak_lr=args.lr, warmup_steps=20,
                total_steps=args.steps),
            donate_argnums=(0,))

        dog = Watchdog()
        losses = []
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in batcher.batch_at(step).items()}
            t0 = time.perf_counter()
            state, metrics = jstep(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            losses.append(loss)
            warn = dog.observe(dt)
            if warn:
                print(f"[watchdog] {warn}")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"{dt:.3f}s/step", flush=True)
            if (step + 1) % args.segment_steps == 0:
                ckpt.save(step + 1, state)       # async materialization
        ckpt.wait()
        print(f"done: loss {losses[0]:.3f} → {losses[-1]:.3f} "
              f"({args.steps - start_step} steps)")


if __name__ == "__main__":
    main()
