"""Device tier of the TierStack (devtier.py), on the CPU.

JAX's CPU backend reports no memory statistics, so each test that needs
the tier on gives it a fake device memory: ``FakeHBM`` counts the bytes
of the arrays a test makes through ``alloc`` while they are alive, and
keeps the peak since it was made, as a TPU's ``memory_stats()`` does.

Correctness bar:

* a write-back save of device leaves is served back as the same pytree,
  leaf for leaf, with no copy to the host on either side;
* a resident signature is priced as a handoff, so OMP keeps a value on
  the device that the disk price would have refused;
* before a node computes, unpinned entries are released until the device
  has room for the node's working set beside its programs' scratch, so a
  run that makes a new output each round never passes the device's
  limit; an entry pinned by a planned LOAD survives; a released entry
  goes down to the host tier only where Algorithm 2 holds at its price;
  a node that runs out of device memory all the same runs once more
  with the tier emptied;
* with no memory statistics the tier is off and the old path runs;
* a server's shutdown leaves the tier empty.
"""
import threading
import time
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import IterativeSession, Workflow, devtier, spans
from repro.core.config import StoreConfig
from repro.core.dag import State
from repro.core.store import Store


class FakeHBM:
    """A device memory for ``devtier.device_stats``: the bytes in use are
    the sizes of the live arrays made by :meth:`alloc`."""

    def __init__(self, limit: int):
        self.limit = int(limit)
        self.in_use = 0
        self.peak = 0
        self.reserved = 0          # program scratch, outside in_use
        self._lock = threading.Lock()

    def alloc(self, nbytes: int, fill: float = 0.0) -> jax.Array:
        """An array of ``nbytes``, counted while it is alive."""
        x = jnp.full((nbytes // 4,), fill, jnp.float32)
        with self._lock:
            self.in_use += nbytes
            self.peak = max(self.peak, self.in_use)
        weakref.finalize(x, self._free, nbytes)
        return x

    def _free(self, nbytes: int) -> None:
        with self._lock:
            self.in_use -= nbytes

    def stats(self, devices) -> list:
        with self._lock:
            return [{"bytes_limit": self.limit, "bytes_in_use": self.in_use,
                     "peak_bytes_in_use": self.peak,
                     "bytes_reserved": self.reserved,
                     "peak_bytes_reserved": self.reserved}
                    for _ in devices]


@pytest.fixture
def hbm(monkeypatch):
    fake = FakeHBM(16 << 20)
    monkeypatch.setattr(devtier, "device_stats", fake.stats)
    return fake


def _wb_store(root) -> Store:
    return Store(str(root), mem_budget_bytes=64e6, mem_writeback=True)


def _spans_since(n0: int, *names: str) -> list:
    return [s for s in spans.recorded()[n0:] if s.name in names]


def _value() -> dict:
    return {"state": {"w": jnp.arange(8.0), "m": jnp.ones((2, 3))},
            "losses": np.asarray([1.0, 0.5])}


@pytest.mark.parametrize("save", ["save", "save_enqueue"])
def test_writeback_save_of_device_pytree_is_served_back_as_is(tmp_path,
                                                              hbm, save):
    store = _wb_store(tmp_path)
    value = _value()
    n0 = len(spans.recorded())
    info = getattr(store, save)("ab12", "train", value,
                                extra_meta={"compute_s": 1.0})
    if save == "save_enqueue":
        info = info.result(timeout=10)
    assert info.nbytes == 0                      # nothing reached the disk
    assert not store.has_local("ab12")
    assert not _spans_since(n0, "store.to_host")
    (save,) = _spans_since(n0, "store.save")
    assert save.attrs["tier"] == "device"

    n1 = len(spans.recorded())
    got, _secs = store.load("ab12")
    assert got is value
    placement = {i: leaf.sharding for i, leaf in enumerate(
        jax.tree_util.tree_leaves(value)) if isinstance(leaf, jax.Array)}
    got, _secs = store.load("ab12", sharding_for_leaf=lambda i, shape,
                            dtype: placement.get(i))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(value)):
        assert g is w
    assert {s.attrs["tier"] for s in _spans_since(n1, "store.load")} \
        == {"device"}
    assert not _spans_since(n1, "store.to_device", "store.to_host")
    status = store.tier_status()["device"]
    assert status["hits"] == 2 and status["entries"] == 1
    assert status["budget"] == hbm.limit
    assert store.tier_status()["memory"]["entries"] == 0
    # The write-back barrier writes it to disk and keeps it resident.
    assert store.mem_flush() == 1
    assert store.has_local("ab12") and store._dev.has("ab12")


def _big_workflow(edit: int) -> Workflow:
    """``big`` (30 ms and more, a 100 MB device array; a disk reload
    would cost ~0.2 s) feeds ``out``, the only node an edit changes."""
    wf = Workflow("big")

    def big():
        time.sleep(0.03)
        return {"x": jnp.ones((25_000_000,), jnp.float32)}

    b = wf.source("big", big, config="big")
    out = wf.reducer("out", lambda v: float(v["x"][:4].sum()) + edit, [b],
                     config=("out", edit))
    wf.output(out)
    return wf


def test_device_price_is_a_handoff_and_omp_keeps_it(tmp_path, monkeypatch):
    nbytes = 100e6
    disk = _wb_store(tmp_path / "disk").est_load_seconds(nbytes)
    assert 2 * disk > 0.4                       # Algorithm 2 refuses `big`

    # The old path: no memory statistics, the disk price, no reuse.
    sess = IterativeSession(str(tmp_path / "cpu"),
                            storage=StoreConfig(mem_writeback=True))
    rep = sess.run(_big_workflow(0))
    assert "big" in rep.execution.skipped_mat
    assert "big" not in rep.execution.materialized

    fake = FakeHBM(1 << 40)
    monkeypatch.setattr(devtier, "device_stats", fake.stats)
    sess = IterativeSession(str(tmp_path / "dev"),
                            storage=StoreConfig(mem_writeback=True))
    rep = sess.run(_big_workflow(0))
    assert "big" in rep.execution.materialized
    sig = rep.sigs["big"]
    assert sess.store.est_load_seconds(nbytes, sig=sig) < 1e-3
    assert sess.store.est_load_seconds(nbytes, sig=sig) < disk / 100
    rep2 = sess.run(_big_workflow(1))
    assert rep2.execution.states["big"] is State.LOAD
    assert rep2.outputs["out"] == 5.0


def _dpr_workflow(hbm: FakeHBM, round_: int, unit: int) -> Workflow:
    """``init`` (fixed signature, 5 units) → ``train`` (a new 5-unit
    output each round) → ``eval`` (2 units while it runs)."""
    wf = Workflow("dpr")
    init = wf.source("init", lambda: {"s": hbm.alloc(5 * unit)},
                     config="init")
    data = wf.source("data", lambda: np.full(4, round_, np.float32),
                     config=("data", round_))

    def train(d, s):
        time.sleep(0.01)
        return {"s": hbm.alloc(5 * unit, fill=float(d[0])),
                "loss": np.asarray([float(d[0])])}

    tr = wf.learner("train", train, [data, init], config="train")

    def evaluate(t):
        scratch = hbm.alloc(2 * unit)
        del scratch
        return float(t["s"][0])

    out = wf.reducer("eval", evaluate, [tr], config="eval")
    wf.output(out)
    return wf


def test_new_output_each_round_stays_under_the_device_limit(tmp_path, hbm):
    """Limit 16 units, 3 of them program scratch. ``eval`` is new in the
    first round, so it gets room for the largest working set recorded
    and ``init`` goes; from the second round on ``init`` is kept (an
    input the running ``train`` holds, then a planned LOAD) and each
    round's old output is dropped before the new one is made."""
    unit = hbm.limit // 16
    hbm.reserved = 3 * unit
    hbm.alloc(5 * unit)            # compiled once: `init` is cheap to redo
    sess = IterativeSession(str(tmp_path),
                            storage=StoreConfig(mem_writeback=True))
    for r in range(6):
        rep = sess.run(_dpr_workflow(hbm, r, unit))
        assert rep.outputs["eval"] == float(r)
        if r >= 2:
            assert rep.execution.states["init"] is State.LOAD
        assert hbm.peak <= hbm.limit - hbm.reserved, f"round {r}"
    status = sess.store.tier_status()["device"]
    assert status["hits"] == 4                  # init, rounds 2-5
    assert status["releases"] >= 5              # an old output per round
    assert sess.store.has(rep.sigs["init"])
    assert sess.store.has(rep.sigs["train"])


def test_room_counts_program_scratch(tmp_path, hbm):
    """Buffers alone would fit (6 units free for a 5-unit working set),
    but the programs' 3 units of scratch would not: the entry goes."""
    store = _wb_store(tmp_path)
    unit = hbm.limit // 16
    store.save("ab01", "old", {"x": hbm.alloc(10 * unit)},
               extra_meta={"compute_s": 1e-6})
    with store.device_compute("next"):
        hbm.alloc(5 * unit)                 # a 5-unit working set
    assert store._dev.working_set["next"] == 5 * unit
    assert store.has("ab01")
    hbm.reserved = 3 * unit
    with store.device_compute("next"):
        pass
    assert not store.has("ab01")


def test_pinned_entry_survives_release(tmp_path, hbm):
    store = _wb_store(tmp_path)
    unit = hbm.limit // 16
    store.save("aa01", "a", {"x": hbm.alloc(6 * unit)},
               extra_meta={"compute_s": 1e-6})
    store.save("bb02", "b", {"x": hbm.alloc(6 * unit)},
               extra_meta={"compute_s": 5.0})
    pin = store.acquire_read("aa01")             # a planned LOAD of a
    store._dev.working_set["next"] = 8 * unit
    try:
        with store.device_compute("next"):
            pass
        assert store.has("aa01")                 # pinned: kept
        assert not store._dev.has("bb02")        # the only other: released
    finally:
        pin.release()
    assert store.tier_status()["device"]["leases"]["pins"] == 0


def test_release_drops_cheap_and_demotes_dear(tmp_path, hbm):
    """Algorithm 2 at the price of a device-host copy: of three released
    entries, the one dear to recompute and loaded before goes down to the
    host tier; the cheap one and the one never loaded are dropped."""
    store = _wb_store(tmp_path)
    unit = hbm.limit // 16
    # 5 MB at a device-host copy's ~1.5 GB/s: l_host ~ 3.6e-3 s.
    for sig, cost in (("cc01", 1e-5), ("dd02", 10.0), ("ee03", 10.0)):
        store.save(sig, sig, {"x": hbm.alloc(5 * unit)},
                   extra_meta={"compute_s": cost})
    for sig in ("cc01", "dd02"):
        store.load(sig)
    store._dev.working_set["next"] = 15 * unit
    with store.device_compute("next"):
        pass
    # Dropped: no tier has them, so they are recomputed.
    for sig in ("cc01", "ee03"):
        assert not store.has(sig)
        with pytest.raises(FileNotFoundError):
            store.load(sig)
    # The dear one went down to the host tier, leaves and all.
    assert not store._dev.has("dd02") and store.has("dd02")
    got, _ = store.load("dd02")
    assert isinstance(got["x"], np.ndarray)
    assert got["x"].nbytes == 5 * unit and not got["x"].any()
    status = store.tier_status()["device"]
    assert (status["releases"], status["drops"]) == (3, 2)
    assert hbm.in_use == 0


def test_out_of_memory_releases_the_tier_and_runs_once_more(tmp_path,
                                                            hbm):
    """A node that runs out of device memory (its working set never
    recorded) gets every entry that may go released and one more run;
    any other error is raised at once."""
    unit = hbm.limit // 16
    sess = IterativeSession(str(tmp_path),
                            storage=StoreConfig(mem_writeback=True))
    sess.store.save("ab01", "old", {"x": hbm.alloc(12 * unit)},
                    extra_meta={"compute_s": 1e-6})
    calls = []

    def big():
        calls.append(1)
        if hbm.in_use + 8 * unit > hbm.limit:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")
        return {"x": hbm.alloc(8 * unit)}

    wf = Workflow("oom")
    wf.output(wf.source("big", big, config="big"))
    rep = sess.run(wf)
    assert len(calls) == 2
    assert rep.outputs["big"]["x"].shape == (2 * unit,)
    assert not sess.store.has("ab01")
    assert sess.store.tier_status()["device"]["releases"] == 1

    def broken():
        calls.append(1)
        raise ValueError("not about memory")

    wf = Workflow("broken")
    wf.output(wf.source("broken", broken, config="broken"))
    with pytest.raises(ValueError):
        sess.run(wf)
    assert len(calls) == 3


def test_no_memory_stats_keeps_the_old_path(tmp_path):
    store = _wb_store(tmp_path)
    assert jax.local_devices()[0].memory_stats() is None    # the CPU
    value = _value()
    n0 = len(spans.recorded())
    store.save("ee01", "train", value)
    assert _spans_since(n0, "store.to_host")
    (save,) = _spans_since(n0, "store.save")
    assert save.attrs["tier"] == "memory"
    got, _ = store.load("ee01")
    assert isinstance(got["state"]["w"], np.ndarray)
    assert store.tier_status()["device"] is None
    assert store.est_reload_seconds(value, 40e6) \
        == store.est_load_seconds(40e6)
    with store.device_compute("n"):
        pass
    assert store._dev.working_set == {}


def test_server_shutdown_empties_the_tier(tmp_path, hbm):
    from repro.serve import InProcessClient, SessionServer

    server = SessionServer(str(tmp_path / "srv"),
                           registry={"big": lambda edit=0:
                                     _big_workflow(edit)},
                           storage=StoreConfig(mem_writeback=True))
    client = InProcessClient(server)
    job = client.submit("big", {"edit": 0})
    assert client.wait(job)["status"] == "done"
    assert client.status()["tiers"]["device"]["entries"] >= 1
    client.shutdown()
    assert server.store.tier_status()["device"]["entries"] == 0


def test_concurrent_pins_and_hits_are_counted(tmp_path, hbm):
    """Many threads pin, load and unpin one entry while others admit and
    release: no pin or hit is lost, and a pinned entry is never
    released."""
    import sys

    store = _wb_store(tmp_path)
    unit = hbm.limit // 16
    store.save("ff01", "hot", {"x": hbm.alloc(unit)},
               extra_meta={"compute_s": 1.0})
    store._dev.working_set["n"] = 15 * unit
    T, N = 12, 40
    errors: list = []

    def reader():
        try:
            for _ in range(N):
                pin = store.acquire_read("ff01")
                store.load("ff01")
                pin.release()
        except BaseException as e:     # recorded, re-raised below
            errors.append(e)

    def churner(t):
        try:
            for i in range(N):
                store.save(f"c{t:02d}{i:03d}", "cold",
                           {"x": hbm.alloc(unit)},
                           extra_meta={"compute_s": 1e-6})
                with store.device_compute("n"):
                    pass
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pins = store.acquire_read("ff01")         # held throughout
        threads = ([threading.Thread(target=reader) for _ in range(T)]
                   + [threading.Thread(target=churner, args=(t,))
                      for t in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert store._dev.has("ff01")
        pins.release()
    finally:
        sys.setswitchinterval(old)
    status = store.tier_status()["device"]
    assert status["hits"] == T * N
    assert status["leases"]["pins"] == 0
    assert status["releases"] >= 1
