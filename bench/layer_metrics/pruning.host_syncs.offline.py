"""Device-to-host fetches per job of the program's pruned passes, from the
window's delta of the kernels.prune.host_sync_bytes histogram; "bytes" is
the bytes they moved per job."""

from kdebench import spans


def read(ctx):
    return spans.host_syncs(ctx)
