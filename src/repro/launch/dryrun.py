import argparse
import dataclasses
import json
import os
import re
import time
import traceback

import jax
import numpy as np

from .. import configs
from ..launch import shapes as shapes_lib
from ..launch.mesh import make_production_mesh

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Collective traffic from post-SPMD optimized HLO, per device.

    For each collective we take the largest typed buffer in the result (for
    async -start ops the result tuple holds operand+result; max = the full
    buffer R) and the replica group size g, then derive:

      operand bytes (the spec's §Roofline convention):
        all-gather R/g · g→R? No: operand = R/g; all-reduce = R;
        reduce-scatter = R·g; all-to-all = R; collective-permute = R.
      wire bytes (ring-algorithm estimate actually crossing links):
        all-gather R·(g−1)/g; all-reduce 2R·(g−1)/g; reduce-scatter
        R·(g−1); all-to-all R·(g−1)/g; collective-permute R.
    """
    operand = {k: 0.0 for k in _COLLECTIVES}
    wire = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        m = _LINE_RE.search(s)
        if not m:
            continue
        op = m.group(2)
        sizes = [_shape_bytes(d, dims)
                 for d, dims in _SHAPE_RE.findall(m.group(1))]
        r = max(sizes) if sizes else 0
        g = 1
        gm = _GROUPS_RE.search(s)
        if gm:
            g = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(s)
            if gl:
                g = len(gl.group(1).split(","))
        g = max(g, 1)
        counts[op] += 1
        if op == "all-gather":
            operand[op] += r / g
            wire[op] += r * (g - 1) / g
        elif op == "all-reduce":
            operand[op] += r
            wire[op] += 2 * r * (g - 1) / g
        elif op == "reduce-scatter":
            operand[op] += r * g
            wire[op] += r * (g - 1)
        elif op == "all-to-all":
            operand[op] += r
            wire[op] += r * (g - 1) / g
        else:  # collective-permute
            operand[op] += r
            wire[op] += r
    return {"operand_bytes": operand, "wire_bytes": wire, "counts": counts}


def _arg_bytes_per_device(args_sds, in_shardings, n_devices: int) -> int:
    leaves_s = jax.tree_util.tree_leaves(
        args_sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    total = 0
    flat_shard = jax.tree_util.tree_leaves(
        in_shardings, is_leaf=lambda x: hasattr(x, "spec") or x is None)
    for sds, sh in zip(leaves_s, flat_shard):
        nbytes = int(np.prod(sds.shape)) * sds.dtype.itemsize
        if sh is not None and hasattr(sh, "num_devices_sharded_over"):
            pass
        if sh is not None and hasattr(sh, "spec"):
            used = 1
            sizes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
            for entry in sh.spec:
                if entry is None:
                    continue
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    used *= sizes[a]
            nbytes //= used
        total += nbytes
    return total


def model_flops(cfg, shape_name: str, sh=None) -> float:
    """Analytic 6·N·D (train) / 2·N·D (inference) model FLOPs, global."""
    sh = sh or shapes_lib.SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if sh.kind == "train":
        tokens = sh.batch * sh.seq
        return 6.0 * n_active * tokens
    if sh.kind == "prefill":
        tokens = sh.batch * sh.seq
        return 2.0 * n_active * tokens
    return 2.0 * n_active * sh.batch  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             ruleset: str | None = None, remat: str | None = None,
             grad_accum: int | None = None, attn_impl: str | None = None,
             embed_impl: str | None = None, xent_impl: str | None = None,
             moe_impl: str | None = None, window_cache: bool = False,
             probe: bool = False,
             out_dir: str = "results/dryrun", tag: str = "") -> dict:
    cfg = configs.get(arch)
    overrides = {}
    if remat is not None:
        overrides["remat"] = remat
    if grad_accum is not None:
        overrides["grad_accum"] = grad_accum
    if attn_impl is not None:
        overrides["attn_impl"] = attn_impl
    if embed_impl is not None:
        overrides["embed_impl"] = embed_impl
    if xent_impl is not None:
        overrides["xent_impl"] = xent_impl
    if moe_impl is not None:
        overrides["moe_impl"] = moe_impl
    if window_cache:
        overrides["window_cache"] = True
    accum_scale = 1
    if probe:
        # Cost-accurate probe: XLA's cost_analysis (and the HLO text) count
        # while-loop bodies ONCE, so scanned models under-report. The probe
        # unrolls the layer stack and runs ONE microbatch; a reader scales
        # the per-microbatch terms back up by accum_scale (the real
        # grad_accum).
        overrides["unroll"] = True
        accum_scale = overrides.get("grad_accum", cfg.grad_accum)
        overrides["grad_accum"] = 1
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "ruleset": ruleset, "overrides": overrides, "tag": tag,
        "probe": probe, "accum_scale": accum_scale,
        "ok": False,
    }
    sh0 = shapes_lib.SHAPES[shape_name]
    patched = sh0
    if probe and sh0.kind == "train" and accum_scale > 1:
        # probe one real microbatch; a reader scales terms ×accum_scale
        patched = dataclasses.replace(sh0, batch=sh0.batch // accum_scale)
    t0 = time.perf_counter()
    try:
        shapes_lib.SHAPES[shape_name] = patched
        mesh = make_production_mesh(multi_pod=multi_pod)
        rec["n_devices"] = int(np.prod(mesh.devices.shape))
        fn, args, in_sh, out_sh, donate = shapes_lib.build_step(
            cfg, shape_name, mesh, ruleset_name=ruleset)
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            rec["lower_s"] = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
            rec["compile_s"] = time.perf_counter() - t1
            if os.environ.get("DRYRUN_VERBOSE"):
                print(compiled.memory_analysis())   # proves it fits
                print(compiled.cost_analysis())     # FLOPs/bytes
            try:
                ma = compiled.memory_analysis()
                rec["memory_analysis"] = {
                    k: int(getattr(ma, k)) for k in dir(ma)
                    if k.endswith("_in_bytes") and not k.startswith("_")
                } if ma is not None else None
            except Exception as e:  # CPU backend may not support it
                rec["memory_analysis"] = f"unavailable: {e}"
            try:
                ca = compiled.cost_analysis()
                rec["cost_analysis"] = {
                    k: float(v) for k, v in ca.items()
                    if isinstance(v, (int, float)) and (
                        "flops" in k or "bytes" in k or "utilization" in k)}
            except Exception as e:
                rec["cost_analysis"] = f"unavailable: {e}"
            hlo = compiled.as_text()
            rec["collectives"] = collective_bytes_from_hlo(hlo)
            rec["hlo_bytes"] = len(hlo)
        rec["arg_bytes_per_device"] = _arg_bytes_per_device(
            args, in_sh, rec["n_devices"])
        rec["model_flops_global"] = model_flops(cfg, shape_name, sh=sh0)
        rec["param_count"] = cfg.param_count()
        rec["active_param_count"] = cfg.active_param_count()
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        shapes_lib.SHAPES[shape_name] = sh0
    rec["total_s"] = time.perf_counter() - t0

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(out_dir,
                        f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    # 512 placeholder CPU devices. JAX fixes the device count at its first
    # backend use, so this must precede any computation — and stays out of
    # import time, where it would change the device set of every importer.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--ruleset", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--embed-impl", default=None)
    ap.add_argument("--xent-impl", default=None)
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--window-cache", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="unrolled, single-microbatch cost probe "
                         "(cost_analysis counts a scanned loop body once; "
                         "scale its terms by the record's accum_scale)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = configs.ASSIGNED if (args.all or args.arch is None) \
        else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        cfg = configs.get(arch)
        shp = shapes_lib.cells(cfg) if (args.all or args.shape is None) \
            else [args.shape]
        for shape_name in shp:
            for multi in meshes:
                mesh_name = "pod2x16x16" if multi else "pod16x16"
                suffix = f"_{args.tag}" if args.tag else ""
                path = os.path.join(
                    args.out, f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"[skip] {path}")
                            continue
                rec = run_cell(
                    arch, shape_name, multi_pod=multi, ruleset=args.ruleset,
                    remat=args.remat, grad_accum=args.grad_accum,
                    attn_impl=args.attn_impl, embed_impl=args.embed_impl,
                    xent_impl=args.xent_impl, moe_impl=args.moe_impl,
                    window_cache=args.window_cache,
                    probe=args.probe, out_dir=args.out, tag=args.tag)
                status = "ok" if rec["ok"] else f"FAIL: {rec.get('error')}"
                print(f"[{arch} × {shape_name} × {mesh_name}] {status} "
                      f"(lower {rec.get('lower_s', 0):.1f}s, "
                      f"compile {rec.get('compile_s', 0):.1f}s)", flush=True)


if __name__ == "__main__":
    main()
