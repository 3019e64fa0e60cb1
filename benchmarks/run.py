"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  bench_cumulative_runtime  — paper Fig. 5 / Fig. 9(a,b,e,f): cumulative
      runtime over 10 iterations for each workflow under OPT / AM / NM
      (NM ≈ KeystoneML's materialize-nothing; AM ≈ DeepDive's
      materialize-everything).
  bench_storage             — paper Fig. 9(c,d): store size after the runs.
  bench_state_fractions     — paper Fig. 8: prune/load/compute fractions,
      OPT vs AM (OPT should match AM's reuse without AM's storage).
  bench_optimizer_overhead  — OEP max-flow solve time vs DAG size (the
      optimizer must be negligible next to operator runtimes).
  bench_parallel_speedup    — sequential engine (max_workers=1, the paper's
      §5.3 discipline) vs the pipelined ready-set engine (worker pool +
      LOAD prefetch + async writer queue) on workflows with branch
      parallelism, reported next to the Fig. 5 numbers.
  bench_sweep_reuse         — ISSUE 2: a K-variant hyperparameter sweep
      sharing one store (concurrent sessions, in-flight dedupe, shared
      budget ledger) vs. K isolated cold runs, on census and MNIST.
      Also verifies no shared-prefix signature was computed twice.
  bench_server_reuse        — ISSUE 3: the session server's global
      shared-prefix-first schedule vs. PR 2's lease-contention FIFO at
      equal concurrency (K variants, K/2 session slots).
  bench_eviction            — ISSUE 4: evict-to-admit vs
      refuse-on-exhausted at a budget ~50% of the sweep working set,
      store pre-squatted by stale junk; also checks ledger==disk at
      drain.
  bench_remote_reuse        — ISSUE 5: cold-host speedup from a warm
      remote tier (fleet-wide materialization sharing across hosts) on
      the census grid: a 2-host sweep warms the tier (fleet compute-once
      must hold across hosts), then a fresh "host" runs the same grid
      against the warm tier vs. an empty one.
  bench_search_reuse        — ISSUE 7: the reuse-aware SearchDriver vs a
      fixed-batch FIFO sweep at equal arm count on the census grid (the
      tuner's marginal-cost frontier must compute measurably fewer
      nodes), plus a successive-halving run whose early-stopped arms
      must leave zero ledger drift and zero wasted recomputes.
  bench_incremental         — ISSUE 8: daily retrain on an append-mostly
      chunked census source: a 10 % append's spliced delta iteration
      must land under 0.5x the cold full retrain, bit-identically
      (writes results/bench/incremental.csv).
  bench_tier                — ISSUE 9: the store's memory tier on the LM
      training workflow: a warm same-process rerun must serve ≥90 % of
      reused bytes from host RAM with zero ``.npy`` leaf reads on the
      hit path, bit-identically to the cold run; a memory hit must load
      ≥5x faster than a disk reload of the same signature; per-tier
      ledgers must equal bytes held after the runs.
  bench_multitenant         — ISSUE 10: consistent-hash (prefix-affine)
      routing vs seeded-random placement across a 2-shard fleet on
      warm-shard reruns: hash routing must land every repeat submission
      on the shard already holding its prefix (0 recomputes, asserted),
      random placement recomputes prefixes on cold shards; the row
      reports the wall-clock speedup (acceptance bar ≥ 1.3x).

Env knobs: HELIX_BENCH_ITERS (default 10), HELIX_BENCH_WORKFLOWS (csv list),
HELIX_BENCH_PAR_WORKERS (worker-pool width for the pipelined engine),
HELIX_BENCH_SWEEP_VARIANTS (sweep arms, default 4), HELIX_BENCH_SWEEP_SCALE
(input-size scale for the sweep bench, default 1 — CI smoke uses ~0.05),
HELIX_BENCH_LM_STEPS (bench_tier LM train steps, default 4),
HELIX_BENCH_TENANT_FAMILIES
(bench_multitenant workflow families, default 6).
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor

# Pin BLAS to one thread *before* numpy loads: the speedup benchmark
# measures engine-level branch parallelism, which double-counts if BLAS
# also fans out every matmul internally. Applies equally to both engines.
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))
sys.path.insert(0, os.path.dirname(__file__))

from repro.core import IterativeSession, Policy  # noqa: E402
from repro.core.dag import DAG, Node             # noqa: E402
from repro.core import oep                       # noqa: E402
from repro.launch.cache import init_compile_cache  # noqa: E402

import workflows as W                            # noqa: E402

N_ITERS = int(os.environ.get("HELIX_BENCH_ITERS", "10"))
SELECT = os.environ.get("HELIX_BENCH_WORKFLOWS", "census,genomics,nlp,mnist"
                        ).split(",")
BUDGET = 10 * 1024 ** 3    # paper §6.3: 10 GB storage budget
ROOT = os.path.join(os.path.dirname(__file__), os.pardir,
                    "results", "bench")


def _run_policy(wd: W.WorkflowDef, policy: Policy, seed: int = 0):
    """Run N_ITERS iterations; returns (per-iter seconds, reports)."""
    workdir = os.path.join(ROOT, f"{wd.name}_{policy.value}")
    shutil.rmtree(workdir, ignore_errors=True)
    sess = IterativeSession(workdir, policy=policy,
                            storage_budget_bytes=BUDGET)
    knobs = W.iteration_schedule(wd, N_ITERS, seed)
    times, reports = [], []
    for kn in knobs:
        wf = wd.build(kn)
        t0 = time.perf_counter()
        rep = sess.run(wf)
        times.append(time.perf_counter() - t0)
        reports.append(rep)
    return times, reports


_CACHE: dict = {}


def _results(wd: W.WorkflowDef, policy: Policy):
    key = (wd.name, policy)
    if key not in _CACHE:
        _CACHE[key] = _run_policy(wd, policy)
    return _CACHE[key]


def bench_cumulative_runtime() -> None:
    """Fig. 5 / 9: cumulative runtime per workflow per policy."""
    for name in SELECT:
        wd = W.WORKFLOWS[name]
        cum = {}
        for policy in (Policy.NEVER, Policy.ALWAYS, Policy.OPT):
            times, _ = _results(wd, policy)
            cum[policy] = sum(times)
        for policy, total in cum.items():
            speedup = cum[Policy.NEVER] / max(total, 1e-9)
            print(f"{name}_{policy.value}_cumulative,"
                  f"{total * 1e6 / N_ITERS:.0f},"
                  f"total_s={total:.2f};speedup_vs_nm={speedup:.2f}x",
                  flush=True)


def bench_storage() -> None:
    """Fig. 9(c,d): storage snapshots."""
    for name in SELECT:
        wd = W.WORKFLOWS[name]
        for policy in (Policy.ALWAYS, Policy.OPT):
            _, reports = _results(wd, policy)
            final = reports[-1].store_bytes
            peak = max(r.store_bytes for r in reports)
            print(f"{name}_{policy.value}_storage,"
                  f"{final / 1024:.0f},"
                  f"peak_kb={peak / 1024:.0f}", flush=True)


def bench_state_fractions() -> None:
    """Fig. 8: aggregate state distribution across reuse iterations."""
    for name in SELECT:
        wd = W.WORKFLOWS[name]
        for policy in (Policy.OPT, Policy.ALWAYS):
            _, reports = _results(wd, policy)
            comp = sum(r.execution.n_computed for r in reports[1:])
            load = sum(r.execution.n_loaded for r in reports[1:])
            prune = sum(r.execution.n_pruned for r in reports[1:])
            tot = max(comp + load + prune, 1)
            print(f"{name}_{policy.value}_states,"
                  f"{comp},"
                  f"compute={comp / tot:.2f};load={load / tot:.2f};"
                  f"prune={prune / tot:.2f}", flush=True)


def bench_optimizer_overhead() -> None:
    """OEP (max-flow) solve time vs DAG size."""
    rng = np.random.default_rng(0)
    for n in (50, 200, 1000):
        nodes = []
        for i in range(n):
            k = int(min(i, 3))
            parents = tuple(f"n{j}" for j in
                            rng.choice(i, k, replace=False)) if i else ()
            nodes.append(Node(name=f"n{i}", fn=None, parents=parents,
                              is_output=(i == n - 1)))
        dag = DAG(nodes)
        cc = {f"n{i}": float(rng.uniform(0.1, 10)) for i in range(n)}
        lc = {f"n{i}": (float(rng.uniform(0.1, 5))
                        if rng.random() < 0.7 else None) for i in range(n)}
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            oep.plan(dag, cc, lc, original=set())
        dt = (time.perf_counter() - t0) / reps
        print(f"oep_solver_n{n},{dt * 1e6:.0f},nodes={n}", flush=True)


def bench_parallel_speedup() -> None:
    """Sequential vs pipelined engine, wall clock of execute().

    census exercises the paper's Fig. 3 parallel feature extractors;
    mnist runs with 12 independent random-FFT towers (KeystoneML-style
    block featurization + per-tower heads). Each engine runs the same
    3-iteration schedule (cold start + two edits) on a fresh store.
    """
    n_workers = int(os.environ.get("HELIX_BENCH_PAR_WORKERS",
                                   str(max(2, os.cpu_count() or 2))))
    n_iters = 3
    cases = {
        "census": (W.WORKFLOWS["census"], {}),
        # Tower ensemble (KeystoneML block solve): 12 independent
        # fft→head→logits branches. PPR-only edits keep the tower shape
        # stable across the schedule (towers are nondeterministic, so every
        # iteration re-runs the full fan-out — the branch-parallel hot
        # path this benchmark isolates). NOTE: attainable speedup is capped
        # by the host — on SMT-sibling vCPU pairs, FP-SIMD numpy work
        # scales at best ~1.4x even fully parallel; on >=4 distinct cores
        # the tower fan-out exceeds 1.5-2x.
        "mnist": (W.WORKFLOWS["mnist"],
                  dict(knobs0=dataclasses.replace(
                           W.MNISTKnobs(), n_towers=12, n_features=6144,
                           n_images=8000, epochs=4),
                       freqs={"PPR": 1.0})),
    }
    for name, (wd, overrides) in cases.items():
        if overrides:
            wd = dataclasses.replace(wd, **overrides)
        engine_secs = {}
        for mode, workers in (("seq", 1), ("par", n_workers)):
            workdir = os.path.join(ROOT, f"{name}_speedup_{mode}")
            shutil.rmtree(workdir, ignore_errors=True)
            sess = IterativeSession(
                workdir, policy=Policy.OPT, storage_budget_bytes=BUDGET,
                max_workers=workers, prefetch_depth=8,
                async_materialization=(workers > 1))
            secs = 0.0
            for kn in W.iteration_schedule(wd, n_iters, seed=0):
                rep = sess.run(wd.build(kn))
                secs += rep.execution.total_seconds
            engine_secs[mode] = secs
        speedup = engine_secs["seq"] / max(engine_secs["par"], 1e-9)
        print(f"{name}_parallel_speedup,"
              f"{engine_secs['par'] * 1e6 / n_iters:.0f},"
              f"seq_s={engine_secs['seq']:.2f};par_s={engine_secs['par']:.2f};"
              f"workers={n_workers};speedup={speedup:.2f}x", flush=True)


def bench_sweep_reuse() -> None:
    """K-variant sweep, one shared store vs. K isolated cold runs.

    The isolated baseline runs each variant in its own fresh workdir (no
    cross-variant reuse possible — today's "fleet" of independent Helix
    users) with the SAME concurrency as the sweep, so the headline
    speedup isolates reuse rather than thread parallelism (the
    sequential sum is also reported as iso_seq_s for reference). The
    sweep runs all K against one store: the max-flow planner + in-flight
    dedupe turn every shared prefix into one compute and K-1 loads.
    census shares everything up to example assembly; MNIST shares the
    random-FFT featurization via the sweep's pinned nonces (one draw for
    the whole sweep).
    """
    from repro.core import IterativeSession, grid, run_sweep

    n_var = int(os.environ.get("HELIX_BENCH_SWEEP_VARIANTS", "4"))
    sweep_scale = float(os.environ.get("HELIX_BENCH_SWEEP_SCALE", "1"))
    # Grid axes: a learner knob × a result-analysis (PPR) knob. Variants
    # then share prefixes *hierarchically* — every arm shares the data
    # pipeline, arms with equal learner knobs also share the trained model
    # (the Li et al. 2019 pipeline-aware-tuning structure). The learner
    # axis gets ⌈K/2⌉ values, the PPR axis 2.
    regs = [0.03, 0.3, 0.01, 1.0, 0.1, 3.0]
    n_regs = max(1, (n_var + 1) // 2)
    cases = {
        "census": (W.CensusKnobs(n_rows=max(2000,
                                            int(120_000 * sweep_scale))),
                   W.build_census,
                   {"reg": regs[:n_regs], "eval_threshold": [0.5, 0.7]}),
        "mnist": (W.MNISTKnobs(n_images=max(500,
                                            int(12_000 * sweep_scale)),
                               epochs=max(5, int(60 * sweep_scale))),
                  W.build_mnist,
                  {"reg": [r * 1e-2 for r in regs[:n_regs]],
                   "eval_k": [1, 2]}),
    }
    for name, (base, build, axes) in cases.items():
        variants = grid(base, axes, build, name=name)[:n_var]
        knob_list = [v.knobs for v in variants]
        n_eff = len(variants)   # the axes can yield fewer arms than asked
        if n_eff < n_var:
            print(f"# {name}: {n_var} variants requested, grid yields "
                  f"{n_eff}", flush=True)

        def run_isolated(i_kn):
            i, kn = i_kn
            workdir = os.path.join(ROOT, f"{name}_sweep_iso{i}")
            shutil.rmtree(workdir, ignore_errors=True)
            sess = IterativeSession(workdir, storage_budget_bytes=BUDGET)
            t0 = time.perf_counter()
            sess.run(build(kn))
            return time.perf_counter() - t0

        iso_seq = sum(run_isolated(ik) for ik in enumerate(knob_list))
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=n_eff) as pool:
            list(pool.map(run_isolated, enumerate(knob_list)))
        iso_par = time.perf_counter() - t0

        workdir = os.path.join(ROOT, f"{name}_sweep_shared")
        shutil.rmtree(workdir, ignore_errors=True)
        report = run_sweep(workdir, variants,
                           storage_budget_bytes=BUDGET)
        report.raise_errors()
        # fleet-wide compute-once check on shared signatures: coordination
        # failures only (deliberate recompute-cheaper-than-load planner
        # choices are reuse economics, not missed reuse)
        shared_recomputed = report.wasted_recomputes()
        speedup = iso_par / max(report.wall_seconds, 1e-9)
        print(f"{name}_sweep_reuse,"
              f"{report.wall_seconds * 1e6 / n_eff:.0f},"
              f"iso_par_s={iso_par:.2f};iso_seq_s={iso_seq:.2f};"
              f"sweep_s={report.wall_seconds:.2f};"
              f"variants={n_eff};speedup={speedup:.2f}x;"
              f"shared_recomputed={shared_recomputed};"
              f"store_kb={report.store_bytes / 1024:.0f}", flush=True)


def bench_server_reuse() -> None:
    """ISSUE 3: the session server's shared-prefix-first global schedule
    vs. PR 2's lease-contention-only dispatch, at equal concurrency.

    Both paths run the same K-variant grid against one shared store
    through ``run_sweep`` (now a session-server client) with
    ``n_concurrent = K/2`` session slots — the many-users-few-slots
    regime where dispatch order matters. The baseline pins
    ``schedule="fifo"`` + ``horizon=K`` (PR 2's behavior: arrival-order
    dispatch, siblings coordinate by blocking on compute leases, static
    amortization); the server path uses ``schedule="prefix"`` with live
    multiplicity-driven amortization. Variants are submitted in natural
    grid order (siblings adjacent) — the common case and FIFO's worst:
    it burns session slots on lease waits that the global scheduler
    instead fills with independent arms.

    Compute-once must hold in both modes: ``shared_recomputed`` counts
    *coordination failures* (a shared value recomputed although loading
    it was the better plan — must be 0; see
    ``SweepReport.wasted_recomputes``). ``planner_recomputed`` counts
    signatures duplicated *on purpose* because the max-flow planner
    priced recompute below load (tiny extractors) — that is reuse
    economics, not missed reuse; PR 2's lease-blocked siblings loaded
    such values blindly. The headline is pure wall clock.

    Regime note: the ordering win needs session slots ≈ cores. With more
    CPU-bound slots than physical cores, every slot is contended anyway,
    a lease-wait costs nothing, and dispatch order stops mattering —
    keep HELIX_BENCH_SWEEP_VARIANTS/2 near the host's core count.
    """
    from repro.core import grid, run_sweep

    n_var = int(os.environ.get("HELIX_BENCH_SWEEP_VARIANTS", "4"))
    sweep_scale = float(os.environ.get("HELIX_BENCH_SWEEP_SCALE", "1"))
    regs = [0.03, 0.3, 0.01, 1.0, 0.1, 3.0]
    n_regs = max(1, (n_var + 1) // 2)
    cases = {
        "census": (W.CensusKnobs(n_rows=max(2000,
                                            int(120_000 * sweep_scale))),
                   W.build_census,
                   {"reg": regs[:n_regs], "eval_threshold": [0.5, 0.7]}),
        "mnist": (W.MNISTKnobs(n_images=max(500,
                                            int(12_000 * sweep_scale)),
                               epochs=max(5, int(60 * sweep_scale))),
                  W.build_mnist,
                  {"reg": [r * 1e-2 for r in regs[:n_regs]],
                   "eval_k": [1, 2]}),
    }
    for name, (base, build, axes) in cases.items():
        variants = grid(base, axes, build, name=name)[:n_var]
        n_eff = len(variants)
        n_conc = max(2, n_eff // 2)
        walls = {}
        wasted = {}
        deliberate = {}
        for mode in ("fifo", "prefix"):
            workdir = os.path.join(ROOT, f"{name}_server_{mode}")
            shutil.rmtree(workdir, ignore_errors=True)
            report = run_sweep(
                workdir, variants, n_concurrent=n_conc,
                storage_budget_bytes=BUDGET, schedule=mode,
                horizon=float(n_eff) if mode == "fifo" else None)
            report.raise_errors()
            walls[mode] = report.wall_seconds
            wasted[mode] = report.wasted_recomputes()
            deliberate[mode] = sum(
                1 for cnt in report.fleet_computes().values() if cnt > 1
            ) - wasted[mode]
        speedup = walls["fifo"] / max(walls["prefix"], 1e-9)
        print(f"{name}_server_reuse,"
              f"{walls['prefix'] * 1e6 / n_eff:.0f},"
              f"fifo_s={walls['fifo']:.2f};"
              f"prefix_s={walls['prefix']:.2f};"
              f"variants={n_eff};slots={n_conc};"
              f"speedup={speedup:.2f}x;"
              f"shared_recomputed={wasted['prefix']};"
              f"planner_recomputed={deliberate['prefix']}", flush=True)


def bench_eviction() -> None:
    """ISSUE 4: evict-to-admit vs refuse-on-exhausted under a storage
    budget sized to ~50% of the sweep's working set, with the budget
    pre-squatted by stale low-benefit junk (the motivating pathology:
    entries with no recompute-cost metadata and no observed reuse hold
    the budget forever).

    Three runs per workflow: one unconstrained sweep to *measure* the
    working set, then the same grid twice against a junk-filled store at
    half that budget — ``evict_to_admit=False`` (refuse-only baseline:
    nothing can be persisted, in-flight dedupe cannot force-persist
    shared values, so siblings serialize on compute leases and then
    recompute) vs ``True`` (the evictor clears junk, shared prefixes
    persist and are loaded). Reports wall clock, duplicate computes,
    eviction stats, and the ledger-vs-disk drift at drain (must be 0).
    """
    from repro.core import Store, StorageLedger, grid, run_sweep

    n_var = int(os.environ.get("HELIX_BENCH_SWEEP_VARIANTS", "4"))
    sweep_scale = float(os.environ.get("HELIX_BENCH_SWEEP_SCALE", "1"))
    regs = [0.03, 0.3, 0.01, 1.0, 0.1, 3.0]
    n_regs = max(1, (n_var + 1) // 2)
    cases = {
        "census": (W.CensusKnobs(n_rows=max(2000,
                                            int(120_000 * sweep_scale))),
                   W.build_census,
                   {"reg": regs[:n_regs], "eval_threshold": [0.5, 0.7]}),
        "mnist": (W.MNISTKnobs(n_images=max(500,
                                            int(12_000 * sweep_scale)),
                               epochs=max(5, int(60 * sweep_scale))),
                  W.build_mnist,
                  {"reg": [r * 1e-2 for r in regs[:n_regs]],
                   "eval_k": [1, 2]}),
    }
    rng = np.random.default_rng(0)
    for name, (base, build, axes) in cases.items():
        variants = grid(base, axes, build, name=name)[:n_var]
        n_eff = len(variants)
        # 1) measure the working set (unconstrained cold sweep)
        workdir = os.path.join(ROOT, f"{name}_evict_ws")
        shutil.rmtree(workdir, ignore_errors=True)
        ws_report = run_sweep(workdir, variants)
        ws_report.raise_errors()
        ws = max(ws_report.store_bytes, 1)
        budget = max(ws // 2, 1)
        # 2) same grid at 50% budget, store pre-squatted with junk
        chunk = max(512, budget // (8 * 6))   # ≈6 junk entries
        walls, dups, drift = {}, {}, {}
        ev_stats: dict = {}
        for mode in ("refuse", "evict"):
            workdir = os.path.join(ROOT, f"{name}_evict_{mode}")
            shutil.rmtree(workdir, ignore_errors=True)
            store = Store(os.path.join(workdir, "store"))
            junk, i = 0, 0
            while junk < budget:
                junk += store.save(f"junk{i:04d}", "junk",
                                   rng.standard_normal(chunk)).nbytes
                i += 1
            report = run_sweep(workdir, variants,
                               storage_budget_bytes=float(budget),
                               evict_to_admit=(mode == "evict"))
            report.raise_errors()
            walls[mode] = report.wall_seconds
            dups[mode] = sum(c - 1
                             for c in report.fleet_computes().values()
                             if c > 1)
            ev_stats[mode] = report.evictions
            drift[mode] = (StorageLedger(store.ledger_path).used()
                           - store.total_bytes())
        ev = ev_stats["evict"]
        speedup = walls["refuse"] / max(walls["evict"], 1e-9)
        print(f"{name}_eviction,"
              f"{walls['evict'] * 1e6 / n_eff:.0f},"
              f"refuse_s={walls['refuse']:.2f};"
              f"evict_s={walls['evict']:.2f};"
              f"speedup={speedup:.2f}x;variants={n_eff};"
              f"ws_kb={ws / 1024:.0f};budget_kb={budget / 1024:.0f};"
              f"dup_refuse={dups['refuse']};dup_evict={dups['evict']};"
              f"evicted={ev.get('n_evicted', 0)};"
              f"vetoed_live={ev.get('n_vetoed_live', 0)};"
              f"ledger_drift_b={drift['evict']:.0f};"
              f"ledger_drift_refuse_b={drift['refuse']:.0f}", flush=True)


def bench_remote_reuse() -> None:
    """ISSUE 5: cold-host speedup from warm-remote reuse.

    Three phases on the census grid:

    1. **Warm** — a 2-host sweep (separate per-host workdirs, one shared
       remote tier) warms the tier. This phase also proves the cross-host
       protocol: ``fleet_dup`` counts shared signatures blindly computed
       more than once *across hosts* (coordination failures — must be 0;
       deliberate recompute-cheaper-than-load planner choices excluded,
       see ``SweepReport.wasted_recomputes``).
    2. **Cold host, warm remote** — a fresh workdir (nothing local) runs
       the same grid against the warm tier: every reusable prefix is a
       remote fetch instead of a compute.
    3. **Cold host, empty remote** — the same fresh-workdir run against
       an empty tier: the true cold baseline at identical concurrency.

    Headline = phase-3 wall / phase-2 wall (acceptance: ≥ 1.5x).
    ``evict_leased`` is a live probe, not a constant: after the warm
    phase the bench pins a warm entry and attempts a remote eviction of
    it — the count of successful deletes-under-pin is the reported
    number (0 = the lease veto held; ``delete_entry`` must refuse).
    ``evict_vetoed`` is the tier's veto counter over the whole run.
    """
    from repro.core import FsObjectStore, RemoteStore, grid, run_sweep

    n_var = int(os.environ.get("HELIX_BENCH_SWEEP_VARIANTS", "4"))
    sweep_scale = float(os.environ.get("HELIX_BENCH_SWEEP_SCALE", "1"))
    regs = [0.03, 0.3, 0.01, 1.0, 0.1, 3.0]
    n_regs = max(1, (n_var + 1) // 2)
    base = W.CensusKnobs(n_rows=max(2000, int(120_000 * sweep_scale)))
    axes = {"reg": regs[:n_regs], "eval_threshold": [0.5, 0.7]}
    variants = grid(base, axes, W.build_census, name="census")[:n_var]
    n_eff = len(variants)

    # 1) warm the tier from a 2-host fleet (also the dedupe proof)
    remote_root = os.path.join(ROOT, "census_remote_tier")
    shutil.rmtree(remote_root, ignore_errors=True)
    warm_wd = os.path.join(ROOT, "census_remote_warm")
    shutil.rmtree(warm_wd, ignore_errors=True)
    warm = run_sweep(warm_wd, variants, n_hosts=2, remote=remote_root)
    warm.raise_errors()
    fleet_dup = warm.wasted_recomputes()

    # Live probe of the lease-veto invariant: pin a warm entry from a
    # "second host" handle, then try to evict it — the reported number
    # counts successful deletes-under-pin (must stay 0).
    prober = RemoteStore(FsObjectStore(remote_root))
    warm_sigs = sorted(prober.entries())
    evict_leased = 0
    if warm_sigs:
        probe_sig = warm_sigs[0]
        pin = prober.acquire_pin(probe_sig)
        evictor_handle = RemoteStore(FsObjectStore(remote_root))
        if evictor_handle.delete_entry(probe_sig) > 0:
            evict_leased += 1
        evictor_handle.close()
        if pin is not None:
            pin.release()
    prober.close()

    # 2) cold host, warm remote vs 3) cold host, empty remote
    walls = {}
    stats = {}
    for mode, tier in (("warm", remote_root),
                       ("empty", os.path.join(ROOT,
                                              "census_remote_empty"))):
        if mode == "empty":
            shutil.rmtree(tier, ignore_errors=True)
        workdir = os.path.join(ROOT, f"census_remote_cold_{mode}")
        shutil.rmtree(workdir, ignore_errors=True)
        report = run_sweep(workdir, variants, remote=tier)
        report.raise_errors()
        walls[mode] = report.wall_seconds
        stats[mode] = report.remote
    speedup = walls["empty"] / max(walls["warm"], 1e-9)
    veto = stats["warm"].get("n_veto_protected", 0)
    print(f"census_remote_reuse,"
          f"{walls['warm'] * 1e6 / n_eff:.0f},"
          f"cold_s={walls['empty']:.2f};warm_s={walls['warm']:.2f};"
          f"variants={n_eff};speedup={speedup:.2f}x;"
          f"fleet_dup={fleet_dup};"
          f"remote_fetches={stats['warm'].get('n_fetches', 0)};"
          f"evict_leased={evict_leased};evict_vetoed={veto}", flush=True)


def bench_search_reuse() -> None:
    """ISSUE 7: reuse-aware search vs fixed-batch FIFO, equal arm count.

    Phase 1 — **frontier ordering**. The candidate grid is learner-reg ×
    PPR-threshold, enumerated reg-fastest, so consecutive candidates
    *differ* in the expensive knob: a fixed batch of the first K arms
    (``run_sweep``, fifo schedule — the pre-ISSUE-7 workflow of a user
    hand-picking K arms in grid order) trains K distinct models. The
    SearchDriver gets the same budget of K arms over the *whole* grid
    and orders its frontier by the server's marginal-cost estimates:
    after each arm it re-prices the remaining candidates against the
    live store, stays signature-adjacent (same reg, different
    threshold), and trains ~K/2 models. At equal arm count the tuner
    must perform measurably less distinct work: fewer unique signatures
    computed (``saved_sigs`` > 0 — the content-addressed measure; raw
    node-compute counts also reported, but at smoke scale they include
    the planner's deliberate recompute-cheaper-than-load choices on
    tiny extractors) and fewer models trained, with zero wasted
    recomputes.

    Phase 2 — **successive halving**. Four regs race over
    ``train_iters`` levels [iters/5, iters] at eta=2 in eager (ASHA)
    mode: the first two finishers of rung 0 promote and the stragglers
    are cancelled mid-run through the server's cooperative-cancel path.
    The row reports ``ledger_drift_b`` (shared ledger minus on-disk
    bytes after the run — must be 0: early-stopped arms released every
    reservation) and ``wasted`` (blind duplicate computes — must be 0).
    """
    from repro.core import StorageLedger, SweepVariant, run_sweep
    from repro.core.config import EngineConfig
    from repro.core.search import (HalvingConfig, SearchConfig,
                                   SearchDriver)
    from repro.serve import SessionServer

    n_var = int(os.environ.get("HELIX_BENCH_SWEEP_VARIANTS", "4"))
    sweep_scale = float(os.environ.get("HELIX_BENCH_SWEEP_SCALE", "1"))
    regs = [0.03, 0.3, 0.01, 1.0, 0.1, 3.0]
    iters = max(30, int(300 * sweep_scale))
    base = W.CensusKnobs(n_rows=max(2000, int(120_000 * sweep_scale)),
                         train_iters=iters)
    budget = max(2, n_var)
    n_regs = min(len(regs), budget)
    # reg varies fastest: FIFO's first `budget` arms are reg-diverse
    # (each trains its own model); the grid's threshold axis is where
    # the reuse frontier finds signature-adjacent siblings.
    space = [{"reg": r, "eval_threshold": t}
             for t in (0.5, 0.7) for r in regs[:n_regs]]

    def factory(**params):
        return W.build_census(dataclasses.replace(base, **params))

    # 1a) fixed-batch FIFO baseline: the first `budget` arms in grid order
    workdir = os.path.join(ROOT, "census_search_fixed")
    shutil.rmtree(workdir, ignore_errors=True)
    fixed_variants = [
        SweepVariant(name=f"fix{i}", build=(lambda p=p: factory(**p)),
                     knobs=p)
        for i, p in enumerate(space[:budget])]
    fixed = run_sweep(workdir, fixed_variants,
                      engine=EngineConfig(schedule="fifo"),
                      storage=None)
    fixed.raise_errors()
    fixed_nodes = sum(
        r.report.execution.n_computed - len(r.report.execution.deduped)
        for r in fixed.results)
    fixed_sigs = len(fixed.fleet_computes())
    fixed_models = len({v.knobs["reg"] for v in fixed_variants})

    # 1b) the tuner: same budget, whole grid, marginal-cost frontier
    workdir = os.path.join(ROOT, "census_search_tuner")
    shutil.rmtree(workdir, ignore_errors=True)
    server = SessionServer(workdir, registry={"census": factory},
                           engine=EngineConfig(n_sessions=1),
                           poll_interval=0.01)
    try:
        # max_inflight=2 over a 1-slot server: execution stays
        # sequential, but the next pick is submitted while the current
        # arm runs — its shared signatures enter the live multiplicity
        # map, so the leader force-persists them (lease-following) even
        # where cost economics alone would not materialize.
        driver = SearchDriver(
            server, "census", space=space,
            config=SearchConfig(strategy="grid", max_arms=budget,
                                frontier="reuse", max_inflight=2))
        tuned = driver.run()
    finally:
        server.shutdown()
    tuner_nodes = tuned.total_node_computes()
    tuner_sigs = len(tuned.fleet_computes())
    tuner_models = len({a.params["reg"] for a in tuned.arms
                        if a.status != "skipped"})
    print(f"census_search_reuse,"
          f"{tuned.wall_seconds * 1e6 / budget:.0f},"
          f"fixed_sigs={fixed_sigs};tuner_sigs={tuner_sigs};"
          f"saved_sigs={fixed_sigs - tuner_sigs};"
          f"fixed_models={fixed_models};tuner_models={tuner_models};"
          f"fixed_nodes={fixed_nodes};tuner_nodes={tuner_nodes};"
          f"fixed_s={fixed.wall_seconds:.2f};"
          f"tuner_s={tuned.wall_seconds:.2f};"
          f"arms={budget};grid={len(space)};"
          f"wasted={tuned.wasted_recomputes()}", flush=True)

    # 2) eager successive halving over train_iters
    workdir = os.path.join(ROOT, "census_search_halving")
    shutil.rmtree(workdir, ignore_errors=True)
    server = SessionServer(workdir, registry={"census": factory},
                           engine=EngineConfig(n_sessions=2),
                           poll_interval=0.01)
    try:
        driver = SearchDriver(
            server, "census",
            space=[{"reg": r} for r in regs[:4]],
            config=SearchConfig(
                strategy="grid", metric="checkResults.value",
                max_inflight=2,
                halving=HalvingConfig(resource="train_iters",
                                      levels=[max(10, iters // 5), iters],
                                      eta=2.0, eager=True)))
        halved = driver.run()
        drift = (StorageLedger(server.store.ledger_path).used()
                 - server.store.total_bytes())
    finally:
        server.shutdown()
    best = halved.best()
    print(f"census_search_halving,"
          f"{halved.wall_seconds * 1e6 / max(len(halved.arms), 1):.0f},"
          f"rungs={len(halved.rungs)};arms={len(halved.arms)};"
          f"cancelled={halved.n_cancelled()};"
          f"skipped={sum(1 for a in halved.arms if a.status == 'skipped')};"
          f"best_reg={best.base_params['reg'] if best else 'na'};"
          f"best_metric={best.metric if best else 'na'};"
          f"ledger_drift_b={drift:.0f};"
          f"wasted={halved.wasted_recomputes()}", flush=True)


def bench_incremental() -> None:
    """ISSUE 8: daily-retrain on an append-mostly source — chunk-spliced
    delta iteration vs. a cold full retrain of the same grown table.

    Warm a store with an ``n_chunks``-chunk census table, append 10 %
    (one chunk), retrain in the warm workdir (delta: map/assoc_reduce
    nodes splice cached chunks, only the appended chunk runs) and in a
    cold workdir (full recompute). Asserts the delta retrain lands under
    0.5× the cold wall-clock and the outputs are bit-identical; writes
    ``results/bench/incremental.csv``.

    Env knobs: HELIX_BENCH_INC_CHUNKS (default 10),
    HELIX_BENCH_INC_ROWS (rows per chunk, default 8000 — CI smoke
    passes something small)."""
    n_chunks = int(os.environ.get("HELIX_BENCH_INC_CHUNKS", "10"))
    rows = int(os.environ.get("HELIX_BENCH_INC_ROWS", "8000"))
    k0 = W.IncrementalCensusKnobs(n_chunks=n_chunks, rows_per_chunk=rows)
    k1 = dataclasses.replace(k0, n_chunks=n_chunks + 1)   # +10 % append

    def timed_run(workdir, knobs, reuse=False):
        if not reuse:
            shutil.rmtree(workdir, ignore_errors=True)
        sess = IterativeSession(workdir, policy=Policy.ALWAYS,
                                storage_budget_bytes=BUDGET)
        t0 = time.perf_counter()
        rep = sess.run(W.build_census_incremental(knobs))
        return time.perf_counter() - t0, rep

    warm_dir = os.path.join(ROOT, "incremental_warm")
    warm_s, _ = timed_run(warm_dir, k0)
    delta_s, delta_rep = timed_run(warm_dir, k1, reuse=True)
    cold_s, cold_rep = timed_run(os.path.join(ROOT, "incremental_cold"),
                                 k1)
    assert delta_rep.outputs["dailyEval"] == cold_rep.outputs["dailyEval"], \
        "delta retrain diverged from cold recompute"
    spliced = sum(delta_rep.execution.chunk_reused.values())
    recomputed = sum(delta_rep.execution.chunk_computed.values())
    ratio = delta_s / max(cold_s, 1e-9)
    os.makedirs(ROOT, exist_ok=True)
    with open(os.path.join(ROOT, "incremental.csv"), "w") as f:
        f.write("scenario,n_chunks,rows_per_chunk,seconds,"
                "chunks_reused,chunks_recomputed\n")
        f.write(f"warm,{n_chunks},{rows},{warm_s:.3f},0,{3 * n_chunks}\n")
        f.write(f"delta,{n_chunks + 1},{rows},{delta_s:.3f},"
                f"{spliced},{recomputed}\n")
        f.write(f"cold,{n_chunks + 1},{rows},{cold_s:.3f},0,"
                f"{3 * (n_chunks + 1)}\n")
    print(f"incremental_daily_retrain,{delta_s * 1e6:.0f},"
          f"delta_s={delta_s:.2f};cold_s={cold_s:.2f};"
          f"ratio={ratio:.2f};spliced={spliced};recomputed={recomputed}",
          flush=True)
    assert ratio < 0.5, (
        f"delta retrain {delta_s:.2f}s not under 0.5x cold {cold_s:.2f}s")


def bench_tier() -> None:
    """ISSUE 9: memory-tier acceptance on the LM training workflow.

    One session, one store, two runs of the identical LM workflow:

    1. **Cold** — trains the small transformer and materializes every
       node (Policy.ALWAYS); the store's write-through memory tier
       admits each durable value on the way to disk.
    2. **Warm (same process)** — reruns the same workflow: every reuse
       is a signature hit that the memory tier must serve zero-copy.

    Asserted, not just reported: the warm run is bit-identical to the
    cold run; ≥90 % of its reused bytes come from the memory tier; the
    warm run's hit path reads **zero** ``.npy`` leaf files; a timed
    memory hit on the largest signature beats a fresh-process disk
    reload of the same signature by ≥5x; and after both runs each
    tier's ledger equals the bytes it actually holds (shared ledger ==
    disk, memory accounting == a recount of resident entries).
    """
    from repro.core import Store, StorageLedger
    from repro.core.config import StoreConfig

    steps = int(os.environ.get("HELIX_BENCH_LM_STEPS", "4"))
    k = dataclasses.replace(W.LMKnobs(), steps=steps)

    workdir = os.path.join(ROOT, "lm_tier")
    shutil.rmtree(workdir, ignore_errors=True)
    sess = IterativeSession(
        workdir, policy=Policy.ALWAYS,
        storage=StoreConfig(budget_bytes=float(BUDGET),
                            shared_budget=True,   # arms the ledger check
                            mem_budget_bytes=256e6))
    store = sess.store

    t0 = time.perf_counter()
    rep_cold = sess.run(W.build_lm(k))
    cold_s = time.perf_counter() - t0

    # Snapshot the counters the warm run must (not) move.
    def stats_snap():
        return {t: dict(s) for t, s in store.load_stats.items()}

    before = stats_snap()
    npy_before = store.npy_leaf_reads
    t0 = time.perf_counter()
    rep_warm = sess.run(W.build_lm(k))
    warm_s = time.perf_counter() - t0
    after = stats_snap()
    npy_delta = store.npy_leaf_reads - npy_before

    assert rep_warm.outputs["evalLoss"] == rep_cold.outputs["evalLoss"], \
        "warm memory-served rerun diverged from the cold run"

    mem_bytes = after["memory"]["bytes"] - before["memory"]["bytes"]
    disk_bytes = after["local"]["bytes"] - before["local"]["bytes"]
    reused = mem_bytes + disk_bytes
    mem_frac = mem_bytes / max(reused, 1)
    assert reused > 0, "warm rerun reused nothing — no signature hits"
    assert mem_frac >= 0.9, (
        f"memory tier served only {mem_frac:.0%} of reused bytes "
        f"({mem_bytes}B mem vs {disk_bytes}B disk)")
    assert npy_delta == 0, (
        f"warm hit path read {npy_delta} .npy leaf files (must be 0)")

    # Timed hit-vs-reload on the largest materialization (the TrainState).
    store.writer_drain()
    big_sig = max(store.entries().items(),
                  key=lambda kv: kv[1].get("nbytes", 0))[0]
    mem_us = min(_timed_load(store, big_sig) for _ in range(5))
    cold_store = Store(store.root, mem_budget_bytes=0.0)
    disk_us = min(_timed_load(cold_store, big_sig) for _ in range(5))
    ratio = disk_us / max(mem_us, 1e-9)
    assert ratio >= 5.0, (
        f"memory hit ({mem_us:.0f}us) only {ratio:.1f}x faster than disk "
        f"reload ({disk_us:.0f}us); need >=5x")

    # Per-tier ledger == bytes held.
    ledger_drift = StorageLedger(store.ledger_path).used() \
        - store.total_bytes()
    tiers = store.tier_status()
    mem_drift = tiers["memory"]["bytes"] - store._mem.recount()
    assert ledger_drift == 0, f"shared ledger drift: {ledger_drift}B"
    assert mem_drift == 0, f"memory-tier accounting drift: {mem_drift}B"

    print(f"lm_tier_warm,{warm_s * 1e6:.0f},"
          f"cold_s={cold_s:.2f};warm_s={warm_s:.2f};"
          f"mem_frac={mem_frac:.2f};npy_reads={npy_delta};"
          f"mem_hit_us={mem_us:.0f};disk_load_us={disk_us:.0f};"
          f"hit_speedup={ratio:.1f}x;"
          f"mem_hits={after['memory']['hits'] - before['memory']['hits']};"
          f"ledger_drift_b={ledger_drift};mem_drift_b={mem_drift}",
          flush=True)


def _timed_load(store, sig: str) -> float:
    t0 = time.perf_counter()
    store.load(sig)
    return (time.perf_counter() - t0) * 1e6


def bench_engine_overlap() -> None:
    """Scheduler-overlap ceiling: a wide diamond of GIL-releasing 150 ms
    wait stubs (no CPU contention). Near-width× speedup means the ready-set
    engine adds no serialization beyond the DAG itself — any gap between
    this and bench_parallel_speedup is hardware contention (shared SMT
    ports / memory bandwidth), not engine overhead."""
    import tempfile

    from repro.core.dag import DAG, Node, State
    from repro.core.executor import execute
    from repro.core.omp import Materializer
    from repro.core.store import Store

    width = 8
    secs = {}
    for workers in (1, width):
        nodes = [Node("src", lambda: 0.0)]
        for i in range(width):
            nodes.append(Node(f"b{i}", lambda x: (time.sleep(0.15), x)[1],
                              parents=("src",)))
        nodes.append(Node("join", lambda *vs: sum(vs),
                          parents=tuple(f"b{i}" for i in range(width)),
                          is_output=True))
        dag = DAG(nodes)
        states = {n: State.COMPUTE for n in dag.nodes}
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            execute(dag, {n: f"sig-{n}" for n in dag.nodes}, states,
                    Store(td), Materializer(policy=Policy.NEVER),
                    max_workers=workers)
            secs[workers] = time.perf_counter() - t0
    print(f"engine_overlap_w{width},{secs[width] * 1e6:.0f},"
          f"seq_s={secs[1]:.2f};par_s={secs[width]:.2f};"
          f"speedup={secs[1] / max(secs[width], 1e-9):.2f}x", flush=True)


def bench_multitenant() -> None:
    """ISSUE 10: consistent-hash routing vs random placement, 2 shards.

    A fleet of two session servers (fair schedule, tenancy on) serves
    N workflow families through a :class:`~repro.serve.FleetRouter`.
    After a warm-up pass places every family's prefix on its rendezvous
    home shard, the same submissions rerun twice against the warm fleet:

    * ``route="hash"`` (the default): every repeat lands on the shard
      already holding its prefix — **zero** prefix recomputes, asserted
      structurally (a fresh router instance is used, proving placement
      is state-free);
    * ``route="random"`` (seeded, the control): placement by coin flip
      sends a fraction of the families to the cold shard, which — with
      no shared remote tier — must recompute their prefixes from
      scratch.

    The row reports both wall clocks and the recompute counts; the
    acceptance bar is hash ≥ 1.3x over random on the warm rerun. Also
    checks each shard's budget ledger still equals its on-disk bytes
    after all three passes (tenancy's scoped reservations reconcile).
    """
    import threading

    from repro.core import StorageLedger
    from repro.core.config import EngineConfig
    from repro.core.workflow import Workflow
    from repro.serve import FleetRouter, SessionServer, TenantSpec

    scale = float(os.environ.get("HELIX_BENCH_SWEEP_SCALE", "1"))
    n_fam = int(os.environ.get("HELIX_BENCH_TENANT_FAMILIES", "6"))
    work = max(40, int(150 * scale))
    dim = 128

    lock = threading.Lock()
    feat_calls: dict[str, int] = {}

    def build(family="f0", reg=0.1):
        wf = Workflow(f"{family}-{reg}")
        src = wf.source(
            "src",
            lambda d=dim: np.arange(d * d, dtype=np.float64).reshape(d, d),
            config=("v1", family))

        def featurize(m, fam=family):
            with lock:
                feat_calls[fam] = feat_calls.get(fam, 0) + 1
            acc = m.copy()
            for _ in range(work):
                acc = np.tanh(acc @ m.T @ m / m.size)
            return acc

        feat = wf.extractor("feat", featurize, [src],
                            config=("feat", family))
        model = wf.learner("model",
                           lambda z, r=reg: float(np.sum(z * z)) * r,
                           [feat], config=("LR", reg))
        out = wf.reducer("eval", lambda m: {"score": m}, [model],
                         config=("eval",))
        wf.output(out)
        return wf

    registry = {"fam": build}
    servers = {}
    for sid in ("s0", "s1"):
        workdir = os.path.join(ROOT, f"multitenant_{sid}")
        shutil.rmtree(workdir, ignore_errors=True)
        servers[sid] = SessionServer(
            workdir, registry=registry,
            tenants={"*": TenantSpec(weight=1.0)},
            engine=EngineConfig(schedule="fair", n_sessions=2),
            poll_interval=0.01)
    arms = [(f"f{i}", 0.1) for i in range(n_fam)]

    def run_all(router):
        jobs = [router.submit("fam", {"family": f, "reg": r})
                for f, r in arms]
        for j in jobs:
            out = router.wait(j, timeout=600.0)
            assert out["status"] == "done", out

    def total_feats():
        with lock:
            return sum(feat_calls.values())

    try:
        run_all(FleetRouter(servers, registry=registry, tenant="warm"))
        warmed = total_feats()
        assert warmed == n_fam, "warm pass must compute each family once"

        t0 = time.perf_counter()
        run_all(FleetRouter(servers, registry=registry, tenant="rerun"))
        hash_s = time.perf_counter() - t0
        hash_recomputed = total_feats() - warmed
        assert hash_recomputed == 0, \
            "hash routing recomputed a cached prefix on a warm fleet"

        seed = int(os.environ.get("HELIX_CHAOS_SEED", "1234"))
        t0 = time.perf_counter()
        run_all(FleetRouter(servers, registry=registry, tenant="rerun",
                            route="random", seed=seed))
        random_s = time.perf_counter() - t0
        random_recomputed = total_feats() - warmed - hash_recomputed

        drift = max(abs(StorageLedger(s.store.ledger_path).used()
                        - s.store.total_bytes())
                    for s in servers.values())
    finally:
        for s in servers.values():
            s.shutdown()

    speedup = random_s / max(hash_s, 1e-9)
    print(f"multitenant_routing,"
          f"{hash_s * 1e6 / len(arms):.0f},"
          f"hash_s={hash_s:.3f};random_s={random_s:.3f};"
          f"speedup={speedup:.2f}x;"
          f"families={n_fam};shards=2;seed={seed};"
          f"hash_recomputed={hash_recomputed};"
          f"random_recomputed={random_recomputed};"
          f"ledger_drift_b={drift:.0f}", flush=True)


def main() -> None:
    bench_cumulative_runtime()
    bench_storage()
    bench_state_fractions()
    bench_optimizer_overhead()
    bench_parallel_speedup()
    bench_sweep_reuse()
    bench_server_reuse()
    bench_eviction()
    bench_remote_reuse()
    bench_search_reuse()
    bench_incremental()
    bench_tier()
    bench_engine_overlap()
    bench_multitenant()


if __name__ == "__main__":
    init_compile_cache()
    if len(sys.argv) > 1:     # run the named benches only
        for bench_name in sys.argv[1:]:
            fn = globals().get(bench_name)
            if not (bench_name.startswith("bench_") and callable(fn)):
                avail = sorted(n for n, v in list(globals().items())
                               if n.startswith("bench_") and callable(v))
                sys.exit(f"unknown benchmark {bench_name!r}; available: "
                         + ", ".join(avail))
            fn()
    else:
        main()
