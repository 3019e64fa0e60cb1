"""Bring-up check: the Helix LM iteration path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded iteration on a 4-chip host

One process runs the phases in order, and each prints one line of its
findings (``<phase>: {json}``); every time in them is host-clock seconds
on the device the line names. With no option:

* ``device``: the first device must be a TPU — there is no CPU fallback;
* ``kernel:*``: the flash-attention, SSD and RMSNorm Pallas kernels at
  published widths, compiled (``tpu_custom_call`` in the compiled text,
  so nothing ran in interpret mode) and checked against their jnp
  references;
* ``iteration``: one Helix iteration client → SessionServer → planner →
  executor → device → store, with internlm2-1.8b at published widths in
  the train node, run cold and then warm after a PPR edit, which must
  load the trained state and reproduce the eval loss bit for bit.

``--chips 4`` runs only the ``sharded`` phase: the same iteration with
the TrainState and batches on a mesh of every chip, compared with the
same steps on one chip of that host.

The last line, ``{"ok": true, "device": {...}}``, is printed only when
every phase passed; a failed phase exits non-zero before it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
sys.path.insert(0, os.path.join(HERE, "benchmarks"))

from repro import configs  # noqa: E402
from repro.core import Policy  # noqa: E402
from repro.core.config import EngineConfig, StoreConfig  # noqa: E402
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref  # noqa: E402
from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref  # noqa: E402
from repro.kernels.ssd import ops as ssd_ops, ref as ssd_ref  # noqa: E402
from repro.launch.cache import init_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models.config import ArchConfig  # noqa: E402
from repro.serve import InProcessClient, SessionServer  # noqa: E402
from workflows import LMKnobs, build_lm, lm_arch  # noqa: E402

WORKDIR = os.path.join(HERE, ".chip_smoke_work")

# Kernel outputs vs their references: max |out - ref| within this share of
# max |ref| — a few bf16 ulps (2^-8 relative each).
KERNEL_RTOL = 2e-2
# First train loss vs ln(vocab), the loss of a uniform prediction. The
# 0.02-scale init leaves logits of std ~0.02·sqrt(d_model) (0.9 nats at
# d_model 2048), which lifts the expected loss by about half its square.
FIRST_LOSS_ATOL = 1.0
# Sharded vs one-chip train/eval losses: relative, a few bf16 ulps.
LOSS_RTOL = 1e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Widths of one smoke run: kernels at the ``attn`` / ``ssm`` configs'
    head layouts over ``batch`` × ``seq`` tokens, RMSNorm over
    ``norm_rows`` rows of the attention config's d_model, and the Helix
    iteration's LM workflow knobs."""

    attn: ArchConfig
    ssm: ArchConfig
    batch: int
    seq: int
    norm_rows: int
    lm: LMKnobs


def sizes(reduced: bool = False) -> Sizes:
    """Published widths, or their ``configs.reduced`` CPU rehearsal.

    The iteration cuts internlm2-1.8b from 24 layers to 2, at published
    widths (d_model 2048, 16 q / 8 kv heads, head_dim 128, d_ff 8192,
    vocab 92544). A train step compiled for a described v5e holds a
    5.0 GB TrainState at 2 layers (6.3 GB at 4); the 380M-parameter
    embedding and LM head dominate. In the workflow, ``initState``'s
    value is still held for materialization while ``train`` runs, so the
    node steps a copy of its own and donates that (without donation a
    step holds its input, its predecessor's output and its own result:
    three TrainStates, 15.2 GB). Two TrainStates plus the step's 3.3 GB
    of temporaries come to 13.4 GB at 2 layers, against the chip's
    16 GiB; at 4 layers they would not fit.
    """
    attn = configs.get("internlm2-1.8b")
    ssm = configs.get("mamba2-130m")
    if not reduced:
        return Sizes(attn, ssm, batch=2, seq=2048, norm_rows=8192,
                     lm=LMKnobs(arch=attn.name, reduced=False, n_layers=2,
                                seq_len=2048, batch=2, steps=3))
    return Sizes(configs.reduced(attn), configs.reduced(ssm), batch=2,
                 seq=64, norm_rows=64,
                 lm=LMKnobs(arch=attn.name, reduced=True, n_layers=2,
                            seq_len=64, batch=2, steps=3))


def report(phase: str, **findings) -> None:
    print(f"{phase}: {json.dumps(findings)}", flush=True)


def check(ok: bool, phase: str, why: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED {phase}: {why}")


@contextlib.contextmanager
def compile_meter():
    """Tally backend compiles (count, seconds) and persistent-cache hits."""
    tally = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tally["compiles"] += 1
            tally["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            tally["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield tally
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", "device",
          f"JAX found no TPU (platform {d.platform!r}); no CPU fallback")
    check(len(devs) >= chips, "device",
          f"{chips} chips wanted, {len(devs)} found")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    report("device", **info, jax=jax.__version__,
           compile_cache=jax.config.jax_compilation_cache_dir)
    return info


def phase_kernel(name: str, fn, ref_fn, args: tuple, *, on_tpu: bool,
                 device: str) -> None:
    """Compile ``fn``, run the compiled program, compare with ``ref_fn``
    (f32-exact matmuls) output by output."""
    phase = f"kernel:{name}"
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    custom_call = "tpu_custom_call" in compiled.as_text()
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    run_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_fn)(*args)
    errs, tols = [], []
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
        errs.append(float(np.max(np.abs(o - r))))
        tols.append(KERNEL_RTOL * float(np.max(np.abs(r))))
    report(phase, device=device,
           shapes=[list(a.shape) for a in args],
           max_abs_err=errs, tol=tols, tpu_custom_call=custom_call,
           compile_s=compile_s, run_s=run_s)
    check(custom_call or not on_tpu, phase,
          "no tpu_custom_call in the compiled program")
    check(all(np.isfinite(e) and e <= t for e, t in zip(errs, tols)),
          phase, f"max abs error {errs} over tolerance {tols}")


def phase_kernels(sz: Sizes, *, on_tpu: bool, device: str) -> None:
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    bf16 = jnp.bfloat16
    a = sz.attn
    hd = a.resolved_head_dim
    q = jax.random.normal(keys[0], (sz.batch, sz.seq, a.num_heads, hd), bf16)
    k = jax.random.normal(keys[1], (sz.batch, sz.seq, a.num_kv_heads, hd),
                          bf16)
    v = jax.random.normal(keys[2], k.shape, bf16)
    phase_kernel(
        "flash_attention",
        lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True),
        lambda q, k, v: fa_ref.attention_ref(q, k, v, 0, causal=True),
        (q, k, v), on_tpu=on_tpu, device=device)

    s = sz.ssm.ssm
    heads = s.expand * sz.ssm.d_model // s.head_dim
    x = jax.random.normal(keys[3], (sz.batch, sz.seq, heads, s.head_dim),
                          bf16)
    dt = jax.nn.softplus(
        jax.random.normal(keys[4], (sz.batch, sz.seq, heads)) - 3.0)
    decay = -jnp.exp(jax.random.normal(keys[5], (heads,)) * 0.5)
    B = (jax.random.normal(keys[6], (sz.batch, sz.seq, s.d_state))
         * 0.5).astype(bf16)
    C = (jax.random.normal(keys[7], B.shape) * 0.5).astype(bf16)
    phase_kernel(
        "ssd",
        lambda x, dt, a, B, C: ssd_ops.ssd(x, dt, a, B, C, chunk=s.chunk),
        ssd_ref.ssd_ref, (x, dt, decay, B, C), on_tpu=on_tpu,
        device=device)

    xn = jax.random.normal(keys[0], (sz.norm_rows, a.d_model), bf16)
    w = jax.random.normal(keys[1], (a.d_model,), jnp.float32)
    phase_kernel("rmsnorm", rn_ops.rmsnorm, rn_ref.rmsnorm_ref, (xn, w),
                 on_tpu=on_tpu, device=device)


def run_iteration(knobs: LMKnobs, workdir: str, mesh=None) -> dict:
    """One cold and one warm (PPR-edited) run of the LM workflow on
    ``mesh`` (default: every local device) through a SessionServer and
    its in-process client; returns both summaries with their host-clock
    and compile seconds."""
    shutil.rmtree(workdir, ignore_errors=True)
    server = SessionServer(
        workdir,
        registry={"lm": lambda **p: build_lm(dataclasses.replace(knobs, **p),
                                             mesh)},
        # ALWAYS: the warm run's load of train must not hinge on how the
        # cold run's measured times price materialization. Host RAM for
        # the initial and the trained state (5.0 GB each at published
        # widths): served from the memory tier, a TrainState reload beats
        # recomputing train, whose recorded cost includes its compile;
        # priced at disk bandwidth it may not.
        engine=EngineConfig(policy=Policy.ALWAYS, n_sessions=1),
        storage=StoreConfig(mem_budget_bytes=24e9))
    client = InProcessClient(server)
    runs = {}
    try:
        for run, params in (("cold", {}),
                            ("warm", {"report_percentiles":
                                      not knobs.report_percentiles})):
            with compile_meter() as tally:
                t0 = time.perf_counter()
                summary = client.wait(client.submit("lm", params),
                                      detail=True)
                seconds = time.perf_counter() - t0
            tiers = client.status()["tiers"]
            runs[run] = dict(summary=summary, seconds=seconds,
                             tier_hits={t: tiers[t]["hits"]
                                        for t in ("memory", "local")},
                             **tally)
    finally:
        client.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)
    return runs


def _nodes(run: dict) -> dict:
    return run["summary"].get("execution", {}).get("node_states", {})


def _eval(run: dict) -> dict:
    return run["summary"].get("outputs", {}).get("evalLoss", {})


def check_iteration(phase: str, knobs: LMKnobs, runs: dict) -> None:
    """The cold run computes every node with finite losses starting near
    ln(vocab); the warm run loads ``train`` and reproduces the eval loss
    bit for bit."""
    cold, warm = runs["cold"], runs["warm"]
    for name, run in runs.items():
        check(run["summary"]["status"] == "done", phase,
              f"{name} run {run['summary']['status']}: "
              f"{run['summary'].get('error')}")
    check(set(_nodes(cold).values()) == {"compute"}, phase,
          f"cold run did not compute every node: {_nodes(cold)}")
    losses = _eval(cold)["train_losses"] + [_eval(cold)["eval_loss"]]
    check(all(math.isfinite(x) for x in losses), phase,
          f"non-finite loss in {losses}")
    ln_v = math.log(lm_arch(knobs).vocab_size)
    check(abs(losses[0] - ln_v) <= FIRST_LOSS_ATOL, phase,
          f"first loss {losses[0]} not within {FIRST_LOSS_ATOL} of "
          f"ln(vocab) {ln_v}")
    check(_nodes(warm).get("train") == "load", phase,
          f"warm run did not load train: {_nodes(warm)}")
    check(_nodes(warm).get("evalLoss") == "compute", phase,
          f"warm run did not recompute evalLoss: {_nodes(warm)}")
    check(_eval(warm)["eval_loss"] == _eval(cold)["eval_loss"]
          and _eval(warm)["train_losses"] == _eval(cold)["train_losses"],
          phase, f"warm losses {_eval(warm)} differ from cold "
                 f"{_eval(cold)}")


def _iteration_findings(knobs: LMKnobs, runs: dict, device: str) -> dict:
    out = {"device": device, "arch": knobs.arch, "reduced": knobs.reduced,
           "layers": knobs.n_layers, "batch": knobs.batch,
           "seq": knobs.seq_len, "steps": knobs.steps}
    for name, run in runs.items():
        execution = run["summary"].get("execution", {})
        out[name] = {"seconds": run["seconds"],
                     "compile_s": run["compile_s"],
                     "compiles": run["compiles"],
                     "cache_hits": run["cache_hits"],
                     "nodes": _nodes(run),
                     "node_seconds": execution.get("node_seconds"),
                     "tier_hits": run["tier_hits"],
                     **{k: v for k, v in execution.items()
                        if k in ("n_computed", "n_loaded", "n_pruned")}}
    out["train_losses"] = _eval(runs["cold"]).get("train_losses")
    out["eval_loss"] = _eval(runs["cold"]).get("eval_loss")
    out["warm_eval_loss"] = _eval(runs["warm"]).get("eval_loss")
    return out


def phase_iteration(knobs: LMKnobs, device: str,
                    workdir: str = WORKDIR) -> dict:
    runs = run_iteration(knobs, workdir)
    findings = _iteration_findings(knobs, runs, device)
    report("iteration", **findings)
    check_iteration("iteration", knobs, runs)
    return findings


def phase_sharded(knobs: LMKnobs, device: str,
                  workdir: str = WORKDIR) -> dict:
    """The iteration with the TrainState and batches on a mesh of every
    local device, against the same steps on a mesh of device 0 alone.
    Tokens per step stay those of ``knobs``, as one sequence per device,
    so that the batch divides over the mesh and device 0 alone still
    holds the one-device run."""
    n = len(jax.devices())
    knobs = dataclasses.replace(knobs, batch=n,
                                seq_len=knobs.batch * knobs.seq_len // n)
    runs = {"one_device": run_iteration(
                knobs, workdir, make_local_mesh(jax.devices()[:1])),
            "mesh": run_iteration(knobs, workdir)}
    findings = {"mesh_devices": n}
    for name, rs in runs.items():
        findings[name] = _iteration_findings(knobs, rs, device)
        findings[name]["placement"] = {
            r: _eval(rs[r]).get("placement") for r in rs}
    report("sharded", **findings)
    for name, rs in runs.items():
        check_iteration("sharded", knobs, rs)
        want = n if name == "mesh" else 1
        for run, placed in findings[name]["placement"].items():
            check(placed["min_devices"] == want
                  and (want == 1 or placed["split_leaves"] > 0), "sharded",
                  f"{name} {run} run's state is not spread over {want} "
                  f"devices: {placed}")
    a, b = (np.asarray(_eval(runs[r]["cold"])["train_losses"]
                       + [_eval(runs[r]["cold"])["eval_loss"]])
            for r in ("mesh", "one_device"))
    check(bool(np.all(np.abs(a - b) <= LOSS_RTOL * np.abs(b))), "sharded",
          f"mesh losses {a.tolist()} vs one-device {b.tolist()} beyond "
          f"relative {LOSS_RTOL}")
    return findings


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded iteration and its one-chip "
                         "comparison, on a 4-chip host")
    args = ap.parse_args(argv)
    init_compile_cache()
    info = phase_device(args.chips)
    sz = sizes()
    if args.chips == 1:
        phase_kernels(sz, on_tpu=True, device=info["kind"])
        phase_iteration(sz.lm, info["kind"])
    else:
        phase_sharded(sz.lm, info["kind"])
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
