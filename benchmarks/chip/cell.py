"""A cell by name: ``<config>.<mix>`` → its configuration, traffic and
limits, each read from a data file of its own under this directory."""
from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str, ext: str = ".json") -> dict:
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run trains and evaluates on, read from the config."""

    batch: int
    seq_len: int
    steps: int
    peak_lr: float
    warmup_steps: int
    clip_norm: float
    adamw: dict


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict             # configs/<config>.json as run
    traffic: dict            # traffic/<mix>.json
    limits: dict             # limits/<cell>.json: {number: limit}

    @property
    def chips(self) -> int:
        return int(self.config["chips"])

    @property
    def sizes(self) -> Sizes:
        t = self.config["train"]
        return Sizes(batch=t["batch"], seq_len=t["seq_len"],
                     steps=t["steps"], peak_lr=t["peak_lr"],
                     warmup_steps=t["warmup_steps"],
                     clip_norm=t["clip_norm"], adamw=dict(t["adamw"]))


def load(workload: str, *, test_sizes: bool = False) -> Cell:
    """The cell ``workload``; ``test_sizes`` swaps in the config's
    ``test_sizes`` (narrow widths for the CPU tests; never timed)."""
    if workload.count(".") < 1:
        raise ValueError(f"a cell is named <config>.<mix>, not {workload!r}")
    config_name, mix = workload.rsplit(".", 1)
    config = _load("configs", config_name)
    if test_sizes:
        small = dict(config["test_sizes"])
        train = dict(config["train"])
        for k in ("batch", "seq_len"):
            train[k] = small.pop(k)
        config = {**config, **small, "train": train}
    limits_path = os.path.join(HERE, "limits", workload + ".json")
    limits = {}
    if os.path.exists(limits_path):
        with open(limits_path) as f:
            limits = json.load(f)
    return Cell(workload, config, _load("traffic", mix), limits)


def reference_module(config: dict):
    """The plain float32 reference named by the config."""
    import importlib
    return importlib.import_module("reference." + config["reference"])
