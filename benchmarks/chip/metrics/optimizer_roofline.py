"""Kernels: the ``optimizer`` scope's share of its roofline (the gradient
clip and the AdamW update, bound by HBM bandwidth), in percent. Nothing
to read where no train step ran in the window."""
from scopes import roofline_share


def read(run):
    return roofline_share(run, "optimizer")
