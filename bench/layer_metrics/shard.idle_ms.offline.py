"""Device-idle ms per job, averaged over chips, in the gaps that fall under
the program's distributed.shard.* spans and not under a kernels.* one."""

from kdebench import sharding


def read(ctx):
    return sharding.idle_ms(ctx)
