"""Flops of every pairwise kernel launch in the window over the window and
the chips' published bf16 peak: the whole step's share of the chip (%)."""

from kdebench import layers


def read(ctx):
    return layers.step_mfu(ctx)
