"""Device tier of the TierStack (device → memory → disk → remote).

In write-back mode a saved value whose leaves are ``jax.Array``s stays
where the node left it: in the accelerator's memory. The entry holds the
pytree itself (its host leaves, such as losses and norms, ride along), a
load hit hands that pytree back, and nothing crosses to the host on
either side. Reuse then costs a handoff, which is what
``Store.est_load_seconds`` prices for a resident signature, so OMP keeps
values on the device that it would never write to disk.

Room comes from the device, never from a setting. Every device reports
(``memory_stats()``) its ``bytes_limit``, the ``bytes_in_use`` by
buffers and their ``peak_bytes_in_use``, and, apart from those, the
scratch its programs reserve (``peak_bytes_reserved``, where the backend
reports it). The tier records each node's working set, kept per node
name like a compute cost: the rise of the peak over the bytes in use at
the node's start where the node set a new peak. The device reports its
peak since the process started, so a node that stays under an earlier
peak shows no more than its net growth, which stands until the node
sets a peak. Before a node computes, entries are released, cheapest
first, until the tightest device has room for that working set (the
largest recorded, for a node never seen) beside the largest program
scratch reserved so far.

An entry pinned by a planned LOAD's read lease is never released, nor
one whose value the running job still holds (releasing it would free
nothing). Where a node runs out of device memory all the same (a
working set misjudged, say because an input now comes from the host),
every entry that may go is released and the node runs once more. A
released entry goes down to the host tier only where Algorithm 2 holds at
that tier's price, ``(1 + 1/h)·l_host < C`` (``h``: the loads the entry
has served; ``C``: the ``compute_s`` saved with it); otherwise it is
dropped, and a later request recomputes it. An entry never loaded is
always dropped: nothing says it will be, and a ``C`` measured on a
node's first run may hold its compile time. ``l_host`` is the price
of copying its bytes between the device and the host, the cost both of
the move down and of a later load back. Entries are memory-only
write-back values ("dirty" in the memory tier's terms): no other process
ever saw them, so dropping one loses nothing another process relies on.

Where the devices report no memory statistics (JAX's CPU backend) the
tier is off: it admits nothing, and every save and load takes the memory
tier's path. Like that tier it is process-local.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable

import jax

from .eviction import benefit_density


def device_stats(devices: list) -> list[dict] | None:
    """``memory_stats()`` of each device, or None when any reports none."""
    stats = [d.memory_stats() for d in devices]
    return None if any(s is None for s in stats) else stats


class DevEntry:
    """One resident value (the attributes a memory-tier spill reads)."""

    __slots__ = ("value", "nbytes", "name", "meta", "pins", "loads",
                 "created")

    def __init__(self, value: Any, nbytes: int, name: str, meta: dict):
        self.value = value
        self.nbytes = int(nbytes)
        self.name = name
        self.meta = dict(meta)
        self.pins = 0
        self.loads = 0
        self.created = time.time()


class DevTier:
    """Process-local device tier of one write-back
    :class:`~repro.core.store.Store`.

    ``est_host_load(nbytes)`` prices the host (memory) tier below;
    ``demote(sig, entry)`` moves a released entry there."""

    def __init__(self, est_host_load: Callable[[float], float],
                 demote: Callable[[str, DevEntry], None]):
        self._est_host_load = est_host_load
        self._demote = demote
        self._lock = threading.Lock()
        self._entries: dict[str, DevEntry] = {}
        self._devices: list | None = None     # None until probed
        self.working_set: dict[str, int] = {}  # node name -> bytes
        self.hits = 0
        self.misses = 0
        self.releases = 0
        self.drops = 0

    # -- the devices ---------------------------------------------------------
    def on(self) -> bool:
        """Do this process's devices report memory statistics? Probed
        once, on the first question."""
        if self._devices is None:
            devices = jax.local_devices()
            self._devices = devices if device_stats(devices) else []
        return bool(self._devices)

    def _free(self) -> int:
        """Bytes free for buffers on the tightest device, once its
        programs have the largest scratch they reserved so far."""
        return min(s["bytes_limit"] - s["bytes_in_use"]
                   - s.get("peak_bytes_reserved", 0)
                   for s in device_stats(self._devices))

    # -- admission and lookups ---------------------------------------------
    def would_admit(self, value: Any) -> bool:
        """Would a save of ``value`` land here: is the tier on, and has
        the value a device leaf?"""
        return self.on() and any(isinstance(leaf, jax.Array) for leaf
                                 in jax.tree_util.tree_leaves(value))

    def admit(self, sig: str, value: Any, nbytes: int, *, name: str = "",
              meta: dict | None = None) -> None:
        """Hold ``value`` as it is (the caller asked
        :meth:`would_admit`)."""
        ent = DevEntry(value, nbytes, name, meta or {})
        with self._lock:
            old = self._entries.get(sig)
            if old is not None:
                # Same signature, same value: keep the pins and evidence.
                ent.pins, ent.loads = old.pins, old.loads
            self._entries[sig] = ent

    def get(self, sig: str) -> DevEntry | None:
        """Hit path: the resident entry (counted) or None."""
        with self._lock:
            ent = self._entries.get(sig)
            if ent is None:
                if self._devices:
                    self.misses += 1
                return None
            ent.loads += 1
            self.hits += 1
            return ent

    def peek(self, sig: str) -> DevEntry | None:
        """Lookup without counting."""
        with self._lock:
            return self._entries.get(sig)

    def has(self, sig: str) -> bool:
        with self._lock:
            return sig in self._entries

    def items(self) -> list[tuple[str, DevEntry]]:
        with self._lock:
            return list(self._entries.items())

    def drop(self, sig: str) -> bool:
        """Remove ``sig`` without demotion (the store deleted it)."""
        with self._lock:
            return self._entries.pop(sig, None) is not None

    def clear(self) -> None:
        """Let go of every entry (server shutdown)."""
        with self._lock:
            self._entries.clear()

    def pin(self, sig: str) -> bool:
        """Pin a resident entry for a planned LOAD; False if absent."""
        with self._lock:
            ent = self._entries.get(sig)
            if ent is None:
                return False
            ent.pins += 1
            return True

    def unpin(self, sig: str) -> None:
        with self._lock:
            ent = self._entries.get(sig)
            if ent is not None and ent.pins > 0:
                ent.pins -= 1

    # -- room for a node ---------------------------------------------------
    @staticmethod
    def _cost(ent: DevEntry) -> float:
        """C(n) saved with the entry."""
        return float(ent.meta.get("compute_s", 0.0) or 0.0)

    def _worth_host(self, ent: DevEntry) -> bool:
        """Algorithm 2 at the host tier's price (a device↔host copy),
        amortized over the loads the entry has served."""
        return ent.loads > 0 and (
            (1.0 + 1.0 / ent.loads) * self._est_host_load(ent.nbytes)
            < self._cost(ent))

    def _pick_victim(self, held) -> tuple[str, DevEntry] | None:
        """Remove and return the entry cheapest to lose (the lowest
        benefit density at the host tier's price) among those neither
        pinned nor ``held``."""
        with self._lock:
            free = [(sig, e) for sig, e in self._entries.items()
                    if e.pins == 0 and sig not in held]
            if not free:
                return None
            sig, ent = min(free, key=lambda it: (benefit_density(
                self._cost(it[1]), self._est_host_load(it[1].nbytes),
                it[1].loads), it[1].created))
            del self._entries[sig]
            self.releases += 1
            return sig, ent

    def make_room(self, name: str, held=frozenset()) -> None:
        """Release entries until the tightest device has room for node
        ``name``'s working set; the signatures in ``held`` are values
        the running job holds. The bytes free are read again after each
        release: a value someone else still holds frees nothing."""
        with self._lock:
            need = self.working_set.get(
                name, max(self.working_set.values(), default=0))
        while need and self._free() < need:
            if not self._release_one(held):
                return

    def release_all(self, held=frozenset()) -> bool:
        """Release every entry neither pinned nor ``held``; True if
        there was one."""
        released = False
        while self._release_one(held):
            released = True
        return released

    def _release_one(self, held) -> bool:
        """Release the cheapest entry that may go: demote or drop it.
        False if none may."""
        victim = self._pick_victim(held)
        if victim is None:
            return False
        sig, ent = victim
        if self._worth_host(ent):
            self._demote(sig, ent)
        else:
            with self._lock:
                self.drops += 1
        ent.value = None
        return True

    def start(self) -> list[tuple[int, int]]:
        """Bytes in use, and the peak so far, on each device as a node
        starts."""
        return [(s["bytes_in_use"], s["peak_bytes_in_use"])
                for s in device_stats(self._devices)]

    def record(self, name: str, start: list[tuple[int, int]]) -> None:
        """Record ``name``'s working set, the largest over its devices:
        the rise of the peak over the bytes in use at its start where
        the node set a new peak; else what it recorded before, or, the
        first time, its net growth."""
        peaked, grown = [], []
        for s, (in_use, peak) in zip(device_stats(self._devices), start):
            if s["peak_bytes_in_use"] > peak:
                peaked.append(s["peak_bytes_in_use"] - in_use)
            grown.append(s["bytes_in_use"] - in_use)
        with self._lock:
            if peaked:
                self.working_set[name] = max(0, int(max(peaked)))
            elif name not in self.working_set:
                self.working_set[name] = max(0, int(max(grown)))

    # -- observability -----------------------------------------------------
    def status(self) -> dict:
        """Unified per-tier record (``Store.tier_status`` schema), plus
        the releases, drops and largest working set recorded. ``budget``
        is the tightest device's ``bytes_limit``."""
        limit = min(s["bytes_limit"] for s in device_stats(self._devices))
        with self._lock:
            return {
                "name": "device",
                "bytes": sum(e.nbytes for e in self._entries.values()),
                "budget": limit,
                "entries": len(self._entries),
                "leases": {"compute": 0, "waiters": 0,
                           "pins": sum(e.pins for e in
                                       self._entries.values())},
                "hits": self.hits,
                "misses": self.misses,
                "releases": self.releases,
                "drops": self.drops,
                "working_set": max(self.working_set.values(), default=0),
            }
