"""Readings of the control and of planted faults, for setting limits.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed it builds the inputs a run of the cell would compare (the
cold iteration's knobs and the first ``check_samples`` window edits of
each client), puts a substitute in the program's place, and prints one
JSON line per seed: for each substitute, the numbers ``check.readings``
gives against the float32 reference, and ``correct`` as
``check.verdict`` decides it under the cell's committed limits
(``limits/<cell>.json``), which each substitute has to fail:

* ``control``: the reference computed in fp8 (scaled E4M3 forward, E5M2
  cotangents), the step below the configuration's bf16;
* ``half_batch``: the float32 reference training on the first half of
  each batch, the mean taken over it;
* ``altered_token``: the float32 reference evaluating a batch with one
  token altered where it is produced.

A step that returns its state unchanged reads 1 by the leaf measure (no
parameter change against the reference's) and needs no run. Runs on the
machine that holds the chip; ``--test-sizes`` runs the CPU rehearsal.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import numpy as np  # noqa: E402

import cell as cell_lib  # noqa: E402
import check  # noqa: E402
import weights  # noqa: E402
from traffic import Traffic  # noqa: E402
from workflow import EVAL_TOKENS, tokens  # noqa: E402


def _out(sub: check.Reference, knobs, alter: bool = False) -> dict:
    tr = sub.trained(knobs)
    params = tr["params"]
    if alter:
        toks = tokens(sub.config, sub.sizes, knobs.eval_seed, EVAL_TOKENS,
                      1)[0].copy()
        rng = weights.rng_for(knobs.eval_seed, 5)
        r, s = rng.integers(toks.shape[0]), rng.integers(1, toks.shape[1])
        toks[r, s] = (toks[r, s] + 1) % sub.config["vocab_size"]
        from reference import common
        nll = common.eval_nll(sub.model, sub.config, sub.sizes, params, toks,
                              sub.mm)
    else:
        nll = sub.eval_nll(knobs)
    return {"train_losses": tr["losses"], "grad_norms": tr["grad_norms"],
            "update_norms": tr["update_norms"],
            "nll": np.asarray(nll).ravel().tolist()}


def readings(workload: str, seed: int, *, test_sizes: bool = False,
             limits: dict | None = None) -> dict:
    """``{substitute: {"numbers": {...}, "correct": bool}}``, judged under
    ``limits`` (default: the cell's committed limits)."""
    c = cell_lib.load(workload, test_sizes=test_sizes)
    model = cell_lib.reference_module(c.config)
    traffic = Traffic(c.traffic, seed)
    n = int(c.traffic["check_samples"])
    knobs = [traffic.base] + [traffic.knobs(cl, i)
                              for cl in range(traffic.clients)
                              for i in range(-(-n // traffic.clients))][:n]
    ref = check.Reference(c.config, c.sizes, model)
    subs = {
        "control": (check.Reference(c.config, c.sizes, model, mm="fp8"),
                    False),
        "half_batch": (check.Reference(c.config, c.sizes, model,
                                       keep_rows=c.sizes.batch // 2), False),
        "altered_token": (check.Reference(c.config, c.sizes, model), True),
    }
    out = {}
    for name, (sub, alter) in subs.items():
        recs = [{"knobs": k, "status": "done", "latency_s": 0.0,
                 "out": _out(sub, k, alter)} for k in knobs]
        numbers = check.readings(ref, recs[0], recs[1:], n, seed)
        correct, _ = check.verdict(
            numbers, c.limits if limits is None else limits)
        out[name] = {"numbers": numbers, "correct": correct}
        sub._trained.clear()
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--test-sizes", action="store_true")
    args = ap.parse_args(argv)
    if not args.test_sizes:
        from repro.launch.cache import init_compile_cache
        init_compile_cache()
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(args.workload, seed,
                                     test_sizes=args.test_sizes)}),
              flush=True)


if __name__ == "__main__":
    main()
