"""Kernel: the Pallas max-min fill kernel's share of its roofline (%)."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "maxmin", "maxmin_pallas")
