"""Kernels: the ``mlp`` scope's share of its roofline (gate, up and down
projections and the SiLU gate), in percent. The
time includes the train step's recomputed forward pass; the FLOPs do not."""
from scopes import roofline_share


def read(run):
    return roofline_share(run, "mlp")
