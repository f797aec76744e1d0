"""Mean bucket rows over real rows per dispatch, from the engine's
serve.pad_ratio histogram over the window."""

from kdebench import layers


def read(ctx):
    return layers.hist_mean(ctx, "serve.pad_ratio")
