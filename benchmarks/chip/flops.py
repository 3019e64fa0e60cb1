"""Model FLOPs and per-scope work of the step programs, from the config's
plain reference module (``reference/<config["reference"]>.py``).

Each reference module counts its own family in one function,
``scope_work(config, program, batch, seq) -> {scope: (flops, bytes)}``;
its docstring says what is counted and what is left out. Model FLOPs are
2 per multiply-add, causal attention over the lower triangle only, with
no recomputation; training is forward plus backward, 3x the forward. A
program's FLOPs are the sum over its scopes. A module without
``scope_work`` is an error, never a default.
"""
from __future__ import annotations

import cell

TRAIN, EVAL = "helix_train_step", "helix_eval_nll"


def scope_work(config: dict, program: str, batch: int, seq: int) -> dict:
    """``{scope: (flops, bytes)}`` of one run of ``program``."""
    mod = cell.reference_module(config)
    if not callable(getattr(mod, "scope_work", None)):
        raise TypeError(f"reference module {mod.__name__!r} has no "
                        f"scope_work")
    return mod.scope_work(config, program, batch, seq)


def program_flops(config: dict, program: str, batch: int, seq: int) -> float:
    """Model FLOPs of one run of ``program``: the sum over its scopes."""
    return sum(f for f, _ in scope_work(config, program, batch,
                                        seq).values())


def train_step(config: dict, batch: int, seq: int) -> float:
    """One optimizer step over ``batch`` x ``seq`` tokens."""
    return program_flops(config, TRAIN, batch, seq)


def eval_pass(config: dict, batch: int, seq: int) -> float:
    """One forward over ``batch`` x ``seq`` tokens."""
    return program_flops(config, EVAL, batch, seq)


def forward_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one token's forward pass at context ``seq``."""
    return eval_pass(config, 1, seq) / seq
