"""``store.device_hits`` on hand-built spans whose answer was counted by
hand."""
import importlib.util
import os

import pytest

from conftest import CHIP
from repro.core.spans import Span


def _reader():
    spec = importlib.util.spec_from_file_location(
        "chip_metric_store.device_hits",
        os.path.join(CHIP, "metrics", "store.device_hits.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ms(name, job, id_, parent, start, end, **attrs):
    return Span(name, job, id_, parent, "t", int(start * 1e6),
                int(end * 1e6), attrs)


def _spans():
    """Window 1000-3000 ms. Job ``a`` loads twice from the device and
    once from memory, ``b`` once from the device; ``c`` failed and ``d``
    ran before the window, so neither counts."""
    return [
        _ms("server.job", "d", 1, None, 500, 900, status="done"),
        _ms("store.load", "d", 2, 1, 600, 610, tier="device"),
        _ms("server.job", "a", 10, None, 1010, 1900, status="done"),
        _ms("store.load", "a", 11, 10, 1100, 1101, tier="device"),
        _ms("store.load", "a", 12, 10, 1200, 1201, tier="device"),
        _ms("store.load", "a", 13, 10, 1300, 1310, tier="memory"),
        _ms("store.save", "a", 14, 10, 1400, 1401, tier="device"),
        _ms("server.job", "b", 20, None, 2030, 2900, status="done"),
        _ms("store.load", "b", 21, 20, 2100, 2101, tier="device"),
        _ms("server.job", "c", 30, None, 2950, 2990, status="error"),
        _ms("store.load", "c", 31, 30, 2960, 2961, tier="device"),
    ]


def test_device_hits_on_hand_built_spans():
    read = _reader()
    iterations = [{"t_submit": 1.0, "t_done": 1.95},
                  {"t_submit": 2.0, "t_done": 3.0}]
    assert read({"iterations": iterations, "spans": _spans()}) \
        == pytest.approx((2 + 1) / 2)
    assert read({"iterations": [], "spans": _spans()}) is None
