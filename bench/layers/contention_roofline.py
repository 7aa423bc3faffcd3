"""Kernel: the Pallas contention kernel's share of its roofline (%)."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "contention", "contention_pallas")
