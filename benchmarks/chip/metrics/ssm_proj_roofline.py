"""Kernels: the ``ssm_proj`` scope's share of its roofline (the Mamba-2
mixer's in_proj and out_proj matmuls), in percent. The time includes
the train step's recomputed forward pass; the FLOPs do not."""
from scopes import roofline_share


def read(run):
    return roofline_share(run, "ssm_proj")
