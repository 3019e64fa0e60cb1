"""Memory tier of the TierStack (ISSUE 9): zero-copy hits, demotion
accounting, write-back spills, ml_dtypes cross-tier bit-identity.

Correctness bar:

* a same-process hit serves the *same host pytree object* back with zero
  ``.npy`` leaf reads (the zero-copy contract), bit-identical to a disk
  reload by a memory-less Store and to a remote read-through on a fresh
  host — including bf16/fp8 leaves that ride the ``_npy_storage_view``
  uint reinterpretation on disk;
* the memory budget is enforced by demote-not-delete eviction: entries
  pushed out of RAM remain loadable from disk, and the tier's byte
  accounting equals a recount of what is actually resident (the per-tier
  ledger==bytes-held invariant) through arbitrary churn;
* write-back mode keeps saves memory-only (``SaveInfo.nbytes == 0``,
  nothing on disk, no ledger charge) until ``mem_flush`` or demotion
  spills them — at which point ledger == disk again;
* ``tier_status`` speaks one schema for every tier.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.locking import HAVE_FLOCK
from repro.core.remote import FsObjectStore, RemoteStore
from repro.core.store import StorageLedger, Store


def _mem_store(root, budget=64e6, **kw) -> Store:
    return Store(str(root), mem_budget_bytes=budget, **kw)


def _ml_dtypes_value() -> dict:
    """A pytree whose array leaves exercise the uint-view .npy path."""
    import ml_dtypes

    rng = np.random.default_rng(3)
    f32 = rng.standard_normal((32, 16)).astype(np.float32)
    return {
        "bf16": jnp.asarray(f32, jnp.bfloat16),
        "fp8": f32.astype(ml_dtypes.float8_e4m3fn),
        "f32": f32,
        "tag": "mixed",
    }


def _assert_leaves_identical(got: dict, want: dict) -> None:
    for k in ("bf16", "fp8", "f32"):
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype, f"{k}: dtype {g.dtype} != {w.dtype}"
        # bit-level comparison: uint views sidestep NaN!=NaN semantics
        np.testing.assert_array_equal(
            g.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[g.itemsize]),
            w.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[w.itemsize]),
            err_msg=f"leaf {k} not bit-identical")
    assert got["tag"] == want["tag"]


# -- zero-copy hits ----------------------------------------------------------

def test_memory_hit_is_zero_copy_and_skips_npy(tmp_path):
    store = _mem_store(tmp_path)
    value = {"w": np.arange(4096, dtype=np.float64), "k": 7}
    store.save("ab12", "node", value)
    reads0 = store.npy_leaf_reads
    got, secs = store.load("ab12")
    # same host objects back, not a deserialized copy, no disk I/O
    assert got["w"] is value["w"] and got["k"] == 7
    assert store.npy_leaf_reads == reads0
    assert store.load_stats["memory"]["hits"] == 1
    assert store.load_stats["local"]["hits"] == 0
    assert secs >= 0


def test_memory_hit_matches_disk_reload_ml_dtypes(tmp_path):
    value = _ml_dtypes_value()
    store = _mem_store(tmp_path)
    store.save("ab12", "node", value)
    store.writer_drain()

    mem_got, _ = store.load("ab12")               # memory-served
    assert store.load_stats["memory"]["hits"] == 1
    disk_store = Store(str(tmp_path))             # mem off: forces .npy
    disk_got, _ = disk_store.load("ab12")
    assert disk_store.npy_leaf_reads > 0

    _assert_leaves_identical(mem_got, value)
    _assert_leaves_identical(disk_got, value)
    _assert_leaves_identical(mem_got, disk_got)


@pytest.mark.skipif(not HAVE_FLOCK, reason="fleet mode needs POSIX flock")
def test_remote_read_through_promotes_to_memory_ml_dtypes(tmp_path):
    """Host A write-through; host B read-through must be bit-identical
    and land the value in B's memory tier (next load is a RAM hit)."""
    fs = FsObjectStore(str(tmp_path / "bucket"))
    value = _ml_dtypes_value()
    store_a = _mem_store(tmp_path / "hostA", remote=RemoteStore(fs))
    store_a.save("ab12", "node", value)
    store_a.writer_drain()
    assert store_a.remote.exists("ab12")

    store_b = _mem_store(tmp_path / "hostB", remote=RemoteStore(fs))
    got, _ = store_b.load("ab12")                 # remote fetch
    _assert_leaves_identical(got, value)
    assert store_b.load_stats["remote"]["hits"] == 1
    assert store_b.mem_has("ab12")                # promoted on the way in
    reads = store_b.npy_leaf_reads
    again, _ = store_b.load("ab12")               # now a RAM hit
    assert store_b.npy_leaf_reads == reads
    assert store_b.load_stats["memory"]["hits"] == 1
    _assert_leaves_identical(again, got)


def test_disk_promotion_on_local_load(tmp_path):
    """A cold-process load populates the memory tier (read-through
    promotion): the second load of the same signature skips .npy."""
    seed = Store(str(tmp_path))
    seed.save("ab12", "node", {"x": np.ones(512)})
    store = _mem_store(tmp_path)
    assert not store.mem_has("ab12")
    store.load("ab12")
    assert store.mem_has("ab12")
    reads = store.npy_leaf_reads
    store.load("ab12")
    assert store.npy_leaf_reads == reads


# -- budget / demotion accounting --------------------------------------------

def test_demote_not_delete_and_ledger_invariant(tmp_path):
    """Churn far past the memory budget: entries are demoted (never
    lost — disk still serves them) and bytes-held always equals a
    recount of what is resident."""
    store = _mem_store(tmp_path, budget=40_000)
    rng = np.random.default_rng(0)
    for i in range(12):
        store.save(f"sig{i:02d}", f"n{i}",
                   rng.standard_normal(1024))       # ~8KB each
        assert store._mem.bytes_held == store._mem.recount()
        assert store._mem.bytes_held <= 40_000
    status = store.tier_status()["memory"]
    assert status["demotions"] > 0
    assert status["bytes"] == store._mem.recount()
    # demoted != deleted: every signature still loads, bit-identically
    rng = np.random.default_rng(0)
    for i in range(12):
        got, _ = store.load(f"sig{i:02d}")
        np.testing.assert_array_equal(got, rng.standard_normal(1024))


def test_oversized_value_bypasses_memory_tier(tmp_path):
    store = _mem_store(tmp_path, budget=1_000)
    store.save("ab12", "big", np.ones(4096))        # 32KB > budget
    assert not store.mem_has("ab12")
    assert store.has_local("ab12")                  # disk took it
    got, _ = store.load("ab12")
    np.testing.assert_array_equal(got, np.ones(4096))


def test_delete_drops_memory_entry(tmp_path):
    store = _mem_store(tmp_path)
    store.save("ab12", "node", np.ones(64))
    assert store.mem_has("ab12")
    store.delete("ab12")
    assert not store.mem_has("ab12") and not store.has("ab12")


# -- write-back mode ---------------------------------------------------------

def test_writeback_save_is_memory_only_until_flush(tmp_path):
    store = _mem_store(tmp_path, mem_writeback=True)
    # Seed a fleet ledger: the spill path must adjust it to mirror the
    # disk (nobody reserved the spilled bytes — honesty over overshoot).
    StorageLedger(store.ledger_path).ensure(0.0)
    info = store.save("ab12", "node", {"x": np.ones(256)})
    assert info.nbytes == 0                         # no disk charge yet
    assert store.mem_has("ab12") and not store.has_local("ab12")
    assert store.has("ab12")                        # tier-wide presence
    assert store.total_bytes() == 0
    got, _ = store.load("ab12")
    np.testing.assert_array_equal(got["x"], np.ones(256))

    n = store.mem_flush()                           # durability barrier
    assert n == 1
    assert store.has_local("ab12")
    assert store.tier_status()["memory"]["dirty"] == 0
    # ledger == disk after the spill
    ledger = StorageLedger(store.ledger_path).used()
    assert ledger == store.total_bytes() > 0
    disk_got, _ = Store(str(tmp_path)).load("ab12")
    np.testing.assert_array_equal(disk_got["x"], np.ones(256))


def test_writeback_demotion_spills_dirty_entry(tmp_path):
    """Evicting a dirty entry must spill it to disk, not lose it."""
    store = _mem_store(tmp_path, budget=20_000, mem_writeback=True)
    StorageLedger(store.ledger_path).ensure(0.0)
    a = np.arange(1500, dtype=np.float64)           # 12KB
    b = np.arange(1500, 3000, dtype=np.float64)
    store.save("aa11", "a", a)
    store.save("bb22", "b", b)                      # evicts aa11 → spill
    assert store.has_local("aa11")
    assert StorageLedger(store.ledger_path).used() == store.total_bytes()
    got, _ = store.load("aa11")
    np.testing.assert_array_equal(got, a)
    got, _ = store.load("bb22")
    np.testing.assert_array_equal(got, b)


def test_writeback_delete_purges_memory_only_entry(tmp_path):
    store = _mem_store(tmp_path, mem_writeback=True)
    store.save("ab12", "node", np.ones(64))
    assert store.has("ab12") and not store.has_local("ab12")
    store.delete("ab12")
    assert not store.has("ab12") and not store.mem_has("ab12")


# -- unified tier_status schema ----------------------------------------------

_RECORD_KEYS = {"name", "bytes", "budget", "entries", "leases",
                "hits", "misses"}


@pytest.mark.skipif(not HAVE_FLOCK, reason="fleet mode needs POSIX flock")
def test_tier_status_unified_schema(tmp_path):
    fs = FsObjectStore(str(tmp_path / "bucket"))
    store = _mem_store(tmp_path / "host", remote=RemoteStore(fs))
    store.save("ab12", "node", np.ones(256))
    store.writer_drain()
    store.load("ab12")                              # one memory hit
    status = store.tier_status()
    assert list(status) == ["device", "memory", "local", "remote"]
    assert status["device"] is None         # write-through: no device tier
    for tier in ("memory", "local", "remote"):
        rec = status[tier]
        assert rec is not None
        assert _RECORD_KEYS <= set(rec), f"{tier} missing unified keys"
        assert rec["name"] == tier
        assert set(rec["leases"]) == {"compute", "pins", "waiters"}
    assert status["memory"]["hits"] == 1
    assert status["memory"]["entries"] == 1
    assert status["memory"]["bytes"] > 0
    assert status["memory"]["budget"] == pytest.approx(64e6)
    assert status["local"]["entries"] == 1
    assert status["remote"]["entries"] == 1


def test_tier_status_memory_none_when_disabled(tmp_path):
    store = Store(str(tmp_path))
    assert store.tier_status()["memory"] is None


def test_server_status_includes_memory_tier(tmp_path):
    """SessionServer.status()['tiers'] carries the same unified memory
    record (servers default the tier on via StoreConfig)."""
    from repro.serve.server import SessionServer

    server = SessionServer(str(tmp_path / "srv"))
    try:
        tiers = server.status()["tiers"]
        assert tiers["memory"] is not None
        assert _RECORD_KEYS <= set(tiers["memory"])
        assert tiers["memory"]["budget"] == pytest.approx(256e6)
        assert server.status()["store_bytes"] == tiers["local"]["bytes"]
    finally:
        server.shutdown()


# -- per-tier pricing --------------------------------------------------------

def test_est_load_seconds_prices_cheapest_tier(tmp_path):
    store = _mem_store(tmp_path)
    store.save("ab12", "node", np.ones(1 << 16))    # resident in RAM
    nb = store.meta("ab12")["nbytes"]
    mem_est = store.est_load_seconds(nb, sig="ab12")
    disk_est = store.est_load_seconds(nb)           # no sig → durable tier
    assert mem_est < disk_est
    # a signature nowhere near RAM prices at the disk tier
    store._mem.drop("ab12")
    assert store.est_load_seconds(nb, sig="ab12") == disk_est


def test_device_array_offloads_to_host(tmp_path):
    """A jax device array admitted to the tier is offloaded to host RAM
    by the writer queue; the hit still serves a bit-identical value."""
    store = _mem_store(tmp_path)
    value = {"w": jnp.arange(2048, dtype=jnp.float32)}
    store.save("ab12", "node", value)
    store.writer_drain()                            # offload ran
    ent = store._mem.peek("ab12")
    assert ent is not None and not ent.has_device
    leaf = jax.tree_util.tree_leaves(ent.value)[0]
    assert isinstance(leaf, np.ndarray)
    got, _ = store.load("ab12")
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.arange(2048, dtype=np.float32))
