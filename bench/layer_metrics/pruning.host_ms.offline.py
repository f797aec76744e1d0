"""Host ms per job inside the program's kernels.prune.pass spans, less the
kernel launches inside them."""

from kdebench import spans


def read(ctx):
    return spans.host_ms(ctx)
