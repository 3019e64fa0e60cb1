"""The one traffic generator: a mix file names its clients and the knobs
each edit draws anew; every value follows from the run's seed.

Every client runs a closed loop (its next edit goes in when its last
result is back). The base knobs are the cold iteration's; iteration ``i``
of client ``c`` draws each edited knob from ``(seed, knob, c, i)``, and
``i = -1`` is the set-up's warm-up edit, which the window never repeats.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from weights import entropy
from workflow import Knobs

_TAG = {"data_seed": 11, "init_seed": 12, "eval_seed": 13}


def _draw(seed: int, *tags: int) -> int:
    words = np.random.SeedSequence(entropy(seed, *tags)
                                   ).generate_state(2, np.uint32)
    return (int(words[0]) << 31) ^ int(words[1])


class Traffic:
    def __init__(self, mix: dict, seed: int):
        unknown = set(mix["edit"]) - set(_TAG)
        if unknown:
            raise ValueError(f"mix edits unknown knobs {sorted(unknown)}")
        self.clients = int(mix["clients"])
        self.edits = tuple(mix["edit"])
        self.seed = int(seed)
        self.base = Knobs(**{k: _draw(seed, t) for k, t in _TAG.items()})

    def knobs(self, client: int, i: int) -> Knobs:
        return dataclasses.replace(self.base, **{
            k: _draw(self.seed, _TAG[k], client, i + 1) for k in self.edits})
