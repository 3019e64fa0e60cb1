"""Kernels: the ``attention`` scope's share of its roofline (q, k, v and o
projections, RoPE, the causal softmax attention), in percent. The
time includes the train step's recomputed forward pass; the FLOPs do not."""
from scopes import roofline_share


def read(run):
    return roofline_share(run, "attention")
