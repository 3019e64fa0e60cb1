"""Chip benchmark: Helix edit iterations through the session server.

    python3 benchmarks/chip/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

One process on the machine that holds the chip. Set-up (timed as
``setup_s``, from process start): turn on the persistent compile cache,
wipe the store's workdir under the checkout, start a ``SessionServer``
with an in-process client, run the cold iteration (which makes the
weights on the device from the seed and fills the store) and one edit
of the mix from every client at once. Then each client of the mix runs a closed loop of
edits for ``--seconds``: client → server → planner → executor → device
→ store. The window closes when the last iteration submitted before
``--seconds`` has returned, so the rate counts whole iterations over
the whole time they took.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window with the profiler on for its first seconds and reports
the per-layer metrics, the device's busy and window seconds, and a
``breakdown``. After the window the server is shut down and the results
of a seeded sample of iterations are compared with the plain float32
reference (``check.py``). The last stdout line is the result's JSON; the
last stderr lines are each compared number beside its limit. No TPU, or
fewer chips than the cell asks for: exit 2, no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

import cell as cell_lib  # noqa: E402
import check  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402
import devtrace  # noqa: E402
from traffic import Traffic  # noqa: E402
from workflow import Programs, build  # noqa: E402

WORK = os.path.join(ROOT, ".bench_work")
PROGRAMS = ("helix_train_step", "helix_eval_nll")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


def require_chips(n: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"{n} chips wanted, {len(devs)} found")


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


def iterate(client, knobs, *, tag: str = "", iteration: int = -1) -> dict:
    """Submit one edit and wait for it; the record the metrics read."""
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"{tag}.submit", iteration=iteration):
            job = client.submit("lm", dataclasses.asdict(knobs))
        with TraceAnnotation(f"{tag}.wait", iteration=iteration):
            s = client.wait(job, detail=True)
        client.forget(job)
    except Exception as e:  # an iteration that never comes back done
        s = {"status": "error", "error": f"{type(e).__name__}: {e}"}
    t1 = time.perf_counter()
    ex = s.get("execution", {})
    return {"knobs": knobs, "status": s["status"], "error": s.get("error"),
            "t_submit": t0, "t_done": t1, "latency_s": t1 - t0,
            "run_seconds": s.get("run_seconds", 0.0),
            "queued_seconds": s.get("queued_seconds", 0.0),
            "total_seconds": ex.get("total_seconds", 0.0),
            "mat_seconds": ex.get("mat_seconds", 0.0),
            "node_states": ex.get("node_states", {}),
            "node_seconds": ex.get("node_seconds", {}),
            "out": s.get("outputs", {}).get("evalLoss")}


def warm_up(client, traffic: Traffic) -> list:
    """One edit of the mix from every client at once, as the window runs
    them: each session and the planner's cost records see the mix before
    the window does."""
    records: list = [None] * traffic.clients

    def one(c: int):
        records[c] = iterate(client, traffic.knobs(c, -1), tag="setup.warm")

    threads = [threading.Thread(target=one, args=(c,))
               for c in range(traffic.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def window(client, traffic: Traffic, seconds: float,
           trace_dir: str | None, trace_seconds: float) -> tuple:
    """Every client's closed loop for ``seconds``; with ``trace_dir``, the
    profiler covers the first ``trace_seconds``. Returns the records and
    the window's (start, end)."""
    from jax.profiler import TraceAnnotation
    records: list = []
    lock = threading.Lock()
    started = [0] * traffic.clients
    finished = [0] * traffic.clients
    start = time.perf_counter()
    stop = start + seconds

    def loop(c: int):
        i = 0
        while time.perf_counter() < stop:
            started[c] += 1
            rec = iterate(client, traffic.knobs(c, i), tag=f"client{c}",
                          iteration=i)
            with lock:
                records.append(rec)
            finished[c] += 1
            i += 1

    threads = [threading.Thread(target=loop, args=(c,), name=f"client{c}")
               for c in range(traffic.clients)]
    for t in threads:
        t.start()
    if trace_dir is not None:
        with TraceAnnotation(devtrace.WINDOW):
            for t in threads:
                t.join(max(0.0, start + trace_seconds - time.perf_counter()))
        # A span still open when the trace stops is lost: keep tracing
        # until the iterations open at the traced window's end are back.
        open_at_end = list(started)
        while any(f < s for f, s in zip(finished, open_at_end)):
            time.sleep(0.01)
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    end = max((r["t_done"] for r in records), default=time.perf_counter())
    return records, start, end


def load_readers(names: list) -> dict:
    readers = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            f"chip_metric_{name}", os.path.join(HERE, "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        readers[name] = mod.read
    return readers


def manifest_metrics(workload: str) -> tuple[list, list]:
    """The cell's end-to-end and per-layer metrics, by BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        test_sizes: bool = False, require_chip: bool = True,
        plant=None, limits: dict | None = None,
        work: str = WORK) -> dict:
    """One run; returns the result line's object. ``test_sizes``,
    ``require_chip=False`` and a ``work`` directory of their own serve
    the CPU tests; ``plant(programs)`` breaks the timed path underneath
    for the fault tests."""
    c = cell_lib.load(workload, test_sizes=test_sizes)
    if require_chip:
        require_chips(c.chips)
    from repro.core import Policy
    from repro.core.config import EngineConfig, StoreConfig
    from repro.launch.cache import init_compile_cache
    from repro.serve import InProcessClient, SessionServer

    if require_chip:
        init_compile_cache()
        # Cache every program, not only those that take a second to
        # compile, so that a run's set-up compiles nothing after the first.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # The trace's scopes are the executable's own op metadata: an entry
        # compiled from other sources must not stand in for this one.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    e2e, per_layer = manifest_metrics(workload)
    sizes, traffic = c.sizes, Traffic(c.traffic, seed)
    model = cell_lib.reference_module(c.config)
    workdir = os.path.join(work, workload)
    shutil.rmtree(workdir, ignore_errors=True)
    trace_dir = os.path.join(workdir, "trace") if traced else None
    programs = Programs(c.config, sizes, model)
    if plant is not None:
        plant(programs)
    server = SessionServer(
        os.path.join(workdir, "store"),
        registry={"lm": lambda **k: build(programs,
                                          dataclasses.replace(traffic.base,
                                                              **k))},
        engine=EngineConfig(policy=Policy(c.config["engine"]["policy"]),
                            n_sessions=traffic.clients),
        storage=StoreConfig(**c.config["store"]))
    client = InProcessClient(server)
    try:
        with compile_meter() as setup_tally:
            cold = iterate(client, traffic.base, tag="setup.cold")
            warm = warm_up(client, traffic)
        setup_s = time.perf_counter() - T0
        log(f"setup: {setup_s:.3f} s, cold {cold['latency_s']:.3f} s "
            f"({cold['status']}), warm-up "
            f"{[(round(r['latency_s'], 3), r['status']) for r in warm]}; "
            f"{setup_tally}")
        if traced:
            # No Python function tracing: the harness's spans and the
            # device are what the reduction reads.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        with compile_meter() as tally:
            records, start, end = window(
                client, traffic, seconds, trace_dir,
                float(c.traffic["trace_seconds"]))
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        tiers = client.status()["tiers"]
        log("store: " + json.dumps({t: {k: v for k, v in tier.items()
                                        if isinstance(v, (int, float))}
                                    for t, tier in tiers.items() if tier}))
    finally:
        client.shutdown()
    del server, client, programs
    gc.collect()
    done = [r for r in records if r["status"] == "done"]
    for r in sorted(records, key=lambda r: r["t_submit"]):
        log("iteration " + json.dumps({
            **({} if r["status"] == "done"
               else {"status": r["status"], "error": r["error"]}),
            "at": round(r["t_submit"] - start, 3),
            "latency": round(r["latency_s"], 3),
            "executor": round(r["total_seconds"], 3),
            "saves": round(r["mat_seconds"], 3),
            "nodes": {n: [st[0], round(r["node_seconds"].get(n, 0.0), 3)]
                      for n, st in r["node_states"].items()}}))
    log(f"window: {len(records)} iterations ({len(done)} done) in "
        f"{end - start:.3f} s; compiles {tally['compiles']}; device bytes "
        f"in use after shutdown {(dev.memory_stats() or {}).get('bytes_in_use')}")

    t_ref = time.perf_counter()
    ref = check.Reference(c.config, sizes, model)
    numbers = check.readings(ref, cold, records,
                             int(c.traffic["check_samples"]), seed, log=log)
    correct, checks = check.verdict(
        numbers, c.limits if limits is None else limits)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")

    result = {"correct": correct, "attempted": len(records),
              "failed": len(records) - len(done), "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if not traced:
        values = {
            "iterations_per_min": 60.0 * len(done) / (end - start),
            "iteration_p90_s": (float(np.percentile(
                [r["latency_s"] for r in done], 90)) if done else None),
            "setup_s": setup_s}
        for m in e2e:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    else:
        work = {p: flops.scope_work(c.config, p, sizes.batch, sizes.seq_len)
                for p in PROGRAMS}
        found = devtrace.find(trace_dir)
        reduced = (devtrace.reduce(found, PROGRAMS,
                                   {s for w in work.values() for s in w})
                   if found else None)
        data = {"iterations": done, "trace": reduced,
                "compiles": tally["compiles"],
                "flops": {p: sum(f for f, _ in w.values())
                          for p, w in work.items()},
                "work": work,
                "peak": (peaks.peak(dev.device_kind) if require_chip
                         else None)}
        readers = load_readers([m["name"] for m in per_layer])
        for m in per_layer:
            v = readers[m["name"]](data)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            log(f"trace: {json.dumps(reduced)}")
    shutil.rmtree(os.path.join(workdir, "store"), ignore_errors=True)
    result["checks"] = checks
    for name, ch in checks.items():
        log(f"check {name}: {ch['value']} (limit {ch['limit']})")
    return result


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"error: {e}")
        sys.exit(2)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
