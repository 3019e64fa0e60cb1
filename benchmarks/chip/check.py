"""What decides ``correct``: the timed path's own results against the
plain float32 reference, number by number, each under its limit.

Numbers (each the worst over the sampled iterations):

* ``loss_gap``: |program − reference| / |reference| of each train step's
  loss;
* ``grad_gap``, ``update_gap``: per leaf, |‖program‖ − ‖reference‖| over
  max(‖reference‖, the median leaf's), of the first step's clipped
  gradient (as the optimizer got it) and of the parameters' change after
  the last step. Leaves whose reference gradient is under a thousandth
  of the median leaf's move by round-off alone and are left out;
* ``nll_gap``: the widest |program − reference| per-token eval loss, in
  nats;
* ``reload_mismatches``: iterations whose ``train`` node has the cold
  iteration's signature (its state loaded from the store, or computed
  again) and that report another checksum of that state (every bit of
  every leaf, taken on the device in ``evalLoss``) or other training
  logs than the cold iteration: an exact comparison;
* ``failed_iterations``: iterations that never came back done.

The reference takes from the program nothing but its answers: it makes
its own weights and batches from the knobs (:mod:`weights`,
:mod:`workflow`'s token streams), which come from the seed.
"""
from __future__ import annotations

import math

import numpy as np

import jax

import weights
from reference import common
from workflow import EVAL_TOKENS, INIT, TOKENS, Knobs, tokens

SKIP_BELOW = 1e-3        # of the median leaf's reference gradient norm


def sample(records: list, k: int, seed: int) -> list:
    """``k`` of the window's finished records, drawn from the seed, the
    slowest always among them."""
    done = [r for r in records if r["status"] == "done"]
    if len(done) <= k:
        return done
    slowest = max(range(len(done)), key=lambda i: done[i]["latency_s"])
    rest = [i for i in range(len(done)) if i != slowest]
    picked = weights.rng_for(seed, 7).choice(rest, k - 1, replace=False)
    return [done[slowest]] + [done[i] for i in sorted(picked)]


def leaf_gap(prog: dict, ref: dict, keep: set) -> float:
    """The worst leaf's gap of norms; NaN if any leaf reads NaN."""
    med = float(np.median([ref[p] for p in keep]))
    return float(np.max([abs(prog[p] - ref[p]) / max(ref[p], med)
                         for p in keep]))


def kept_leaves(ref_grad: dict) -> set:
    med = float(np.median(list(ref_grad.values())))
    return {p for p, g in ref_grad.items() if g >= SKIP_BELOW * med}


class Reference:
    """Reference results per knob set, computed once each."""

    def __init__(self, config: dict, sizes, model, mm: str = "float32",
                 keep_rows: int | None = None):
        self.config, self.sizes, self.model, self.mm = (config, sizes,
                                                        model, mm)
        self.spec = model.param_spec(config)
        self.keep_rows = keep_rows
        self._trained: dict = {}

    def trained(self, k: Knobs) -> dict:
        key = (k.data_seed, k.init_seed)
        if key not in self._trained:
            params0 = common.f32(_make(_Spec(self.spec),
                                       weights.key_for(k.init_seed, INIT)))
            batches = tokens(self.config, self.sizes, k.data_seed, TOKENS,
                             self.sizes.steps)
            # One trained result at a time: its params hold device memory.
            self._trained = {key: common.train(
                self.model, self.config, self.sizes, params0, list(batches),
                self.mm, keep=self.keep_rows)}
        return self._trained[key]

    def eval_nll(self, k: Knobs, params=None) -> np.ndarray:
        toks = tokens(self.config, self.sizes, k.eval_seed, EVAL_TOKENS, 1)[0]
        return common.eval_nll(self.model, self.config, self.sizes,
                               params if params is not None
                               else self.trained(k)["params"], toks, self.mm)


class _Spec(dict):
    """A parameter spec as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


_make = jax.jit(weights.make, static_argnums=0)


def readings(ref: Reference, cold: dict, window: list, n_sample: int,
             seed: int, log=lambda msg: None) -> dict:
    """The numbers of one run. ``cold`` and each of ``window`` are
    records with ``knobs`` (a Knobs), ``status`` and ``out`` (the
    evalLoss output). ``log`` gets one line per compared iteration."""
    failed = sum(1 for r in window if r["status"] != "done")
    if cold["status"] != "done":
        return {"failed_iterations": failed + 1}
    picked = [cold] + sample(window, n_sample, seed)
    # Train results once per (data, init) pair, sampled ones first so the
    # reference's trained params serve every eval that shares them.
    picked.sort(key=lambda r: (r["knobs"].data_seed, r["knobs"].init_seed))
    gaps = {"loss_gap": [], "grad_gap": [], "update_gap": [], "nll_gap": []}
    for r in picked:
        out, tr = r["out"], ref.trained(r["knobs"])
        keep = kept_leaves(tr["grad_norms"])
        gaps["loss_gap"] += [abs(p - q) / abs(q) for p, q in
                             zip(out["train_losses"], tr["losses"])]
        gaps["grad_gap"].append(leaf_gap(out["grad_norms"],
                                         tr["grad_norms"], keep))
        gaps["update_gap"].append(leaf_gap(out["update_norms"],
                                           tr["update_norms"], keep))
        nll = np.asarray(out["nll"], np.float64)
        want = np.asarray(ref.eval_nll(r["knobs"]), np.float64).ravel()
        gaps["nll_gap"].append(float(np.max(np.abs(nll - want)))
                               if nll.shape == want.shape else math.inf)
        log(f"compared {r['knobs']}: train losses {out['train_losses']} "
            f"vs {tr['losses']}; gaps " + ", ".join(
                f"{k} {v[-1]:.3g}" for k, v in gaps.items()))
    logs = ("state_digest", "train_losses", "grad_norms", "update_norms")
    reloaded = [r for r in window if r["status"] == "done"
                and (r["knobs"].data_seed, r["knobs"].init_seed)
                == (cold["knobs"].data_seed, cold["knobs"].init_seed)]
    mismatches = sum(1 for r in reloaded
                     if any(r["out"].get(k) != cold["out"].get(k)
                            for k in logs))
    # max() would pass over a NaN; a NaN anywhere makes the number one.
    worst = {k: float(np.max(v)) for k, v in gaps.items()}
    return {**worst, "reload_mismatches": mismatches,
            "failed_iterations": failed}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}``. A number with no
    limit, or one that is not finite, fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        finite = math.isfinite(value)
        checks[name] = {"value": value if finite else None, "limit": limit}
        if limit is None or not finite or value > limit:
            ok = False
    for name in sorted(set(limits) - set(numbers)):
        checks[name] = {"value": None, "limit": limits[name]}
        ok = False
    return ok, checks
