"""A named scope's share of its roofline, for the per-layer metrics that
read one.

The least time one run of a program could spend in a set of scopes is
``max(flops / bf16 peak, bytes / HBM bandwidth)`` of their summed work
(the reference module's ``scope_work``); the share is that least time,
summed over the program runs wholly inside the traced window, over the
device seconds the trace attributes to those scopes in the same runs.
Those seconds hold all the scope's work on the device, the forward pass
that the train step recomputes in its backward pass (each block is
rematerialized) included, while the FLOPs are model FLOPs without it: a
share counts useful work, as ``step.mfu`` does.
"""
from __future__ import annotations


def roofline_share(run: dict, *scopes: str) -> float | None:
    """Percent of the roofline reached in ``scopes`` together (scopes that
    XLA fuses into each other are read as one). None where the scopes did
    not run in the window, or where there is no trace or no peak."""
    trace, peak = run["trace"], run["peak"]
    if trace is None or peak is None or "scopes" not in trace:
        return None
    least = seconds = 0.0
    for program, got in trace["scopes"].items():
        spent = sum(got["seconds"].get(s, 0.0) for s in scopes)
        if spent <= 0:
            continue
        work = run["work"][program]
        flops = sum(work[s][0] for s in scopes if s in work)
        nbytes = sum(work[s][1] for s in scopes if s in work)
        least += got["runs"] * max(flops / peak["bf16_flops"],
                                   nbytes / peak["hbm_bytes_per_s"])
        seconds += spent
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
