"""Kernels: the ``ssd`` scope's share of its roofline (the Mamba-2 mixer
between its two projections: the causal conv, dt, the chunked scan, the
D skip and the gated norm), in percent. The time includes the train
step's recomputed forward pass; the FLOPs do not."""
from scopes import roofline_share


def read(run):
    return roofline_share(run, "ssd")
