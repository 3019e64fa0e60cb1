"""The per-layer metrics that read the program's own spans: on a traced
run on the CPU, and on hand-built spans whose answers were counted by
hand."""
import importlib.util
import os

import pytest

import devtrace
from conftest import CHIP, TEST_LIMITS
from repro.core.spans import Span

READERS = ["server.queue_s", "planner.self_s", "store.meta_s",
           "store.transfer_s", "executor.self_s"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name}", os.path.join(CHIP, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_traced_ppr_run_reads_the_program_spans(harness, tmp_path):
    # At test sizes ppr reloads the trained state, so there is a transfer.
    wl = "internlm2-1.8b-2l.ppr"
    r = harness.run(wl, 2**33 + 7, 1.0, True, test_sizes=True,
                    require_chip=False, limits=TEST_LIMITS,
                    work=str(tmp_path))
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in READERS:
        assert m[name]["value"] >= 0, name
    assert m["store.transfer_s"]["value"] > 0
    # The program's spans sit in the trace's host plane on the clock of
    # the harness's own annotations: inside the client's waits.
    from jax.profiler import ProfileData
    path = devtrace.find(os.path.join(tmp_path, wl, "trace"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for line in host.lines for ev in line.events]
    waits = [(s, e) for n, s, e in events if n == "client0.wait"]
    assert waits
    for name in ("server.job", "session.plan", "executor.node"):
        assert any(ws <= s and e <= we for n, s, e in events if n == name
                   for ws, we in waits), name


def _ms(name, job, id_, parent, start, end, **attrs):
    return Span(name, job, id_, parent, "t", int(start * 1e6),
                int(end * 1e6), attrs)


def _hand_built():
    """Window 1000-3000 ms. Jobs ``a`` and ``b`` are window jobs; ``c``
    failed and ``d`` ran before the window, so neither counts."""
    return [
        _ms("server.queue", "d", 1, None, 400, 500),
        _ms("server.job", "d", 2, None, 500, 900, status="done"),
        _ms("meta.txn", "d", 3, 2, 600, 700),
        _ms("server.queue", "a", 10, None, 1000, 1010),
        _ms("server.job", "a", 11, None, 1010, 1900, status="done"),
        _ms("session.init", "a", 12, 11, 1010, 1020),
        _ms("meta.txn", "a", 13, 12, 1012, 1014),
        _ms("session.plan", "a", 14, 11, 1020, 1100),
        _ms("executor.run", "a", 15, 11, 1100, 1800),
        _ms("executor.node", "a", 16, 15, 1100, 1700),
        _ms("store.load", "a", 17, 16, 1100, 1200),
        _ms("store.to_device", "a", 18, 17, 1150, 1200),
        _ms("executor.block", "a", 19, 16, 1200, 1300),
        _ms("executor.decide", "a", 20, 15, 1700, 1710),
        _ms("meta.txn", "a", 21, 20, 1702, 1706),
        _ms("store.save", "a", 22, 15, 1710, 1750),
        _ms("store.to_host", "a", 23, 22, 1710, 1730),
        _ms("meta.txn", "a", 24, 22, 1735, 1745),
        _ms("session.record", "a", 25, 11, 1800, 1890),
        _ms("meta.txn", "a", 26, 25, 1810, 1880),
        _ms("server.queue", "b", 30, None, 2000, 2030),
        _ms("server.job", "b", 31, None, 2030, 2900, status="done"),
        _ms("executor.run", "b", 32, 31, 2100, 2800),
        _ms("executor.node", "b", 33, 32, 2100, 2700),
        _ms("executor.block", "b", 34, 33, 2150, 2250),
        _ms("store.to_device", "b", 35, 33, 2200, 2300),
        _ms("server.queue", "c", 40, None, 2900, 2950),
        _ms("server.job", "c", 41, None, 2950, 2990, status="error"),
        _ms("meta.txn", "c", 42, 41, 2960, 2980),
    ]


# Per window job, in ms: a, b; the metric is their mean.
HAND = {
    "server.queue_s": (10, 30),
    # init 10 - its meta.txn 2, plan 80, record 90 - its meta.txn 70
    "planner.self_s": (8 + 80 + 20, 0),
    "store.meta_s": (2 + 4 + 10 + 70, 0),
    # to_device 50 + block 100 + to_host 20; b's two overlap: 150
    "store.transfer_s": (50 + 100 + 20, 150),
    # run 700 - node, decide and save (650); decide 10 - its meta.txn 4
    "executor.self_s": (700 - 650 + 10 - 4, 700 - 600),
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_built_spans(name):
    read = _reader(name)
    iterations = [{"t_submit": 1.0, "t_done": 1.95},
                  {"t_submit": 2.0, "t_done": 3.0}]
    a, b = HAND[name]
    got = read({"iterations": iterations, "spans": _hand_built()})
    assert got == pytest.approx((a + b) / 2 / 1e3)
    assert read({"iterations": [], "spans": _hand_built()}) is None
