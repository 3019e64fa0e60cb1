"""Kernels: the ``head`` and ``loss`` scopes' share of their roofline
together (the LM head's matmul and the cross-entropy over the vocabulary,
which XLA fuses with it), in percent."""
from scopes import roofline_share


def read(run):
    return roofline_share(run, "head", "loss")
