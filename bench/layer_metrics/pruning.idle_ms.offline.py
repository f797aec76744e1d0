"""Device-idle ms per job in the gaps that fall under the host steps of the
program's pruned passes (its kernels.prune.* spans)."""

from kdebench import spans


def read(ctx):
    return spans.idle_ms(ctx)
