"""The exp rate of the chip, measured: no such rate is published.

A Pallas kernel does nothing but chained f32 ``exp`` on a VMEM-resident
tile.  Its device time in the trace, over the exponentials it computed, is
the rate the exp term of a roofline divides by.  Each step of the chain
is one multiply and one exp, less work per exp than any pairwise kernel
does, so no kernel of the program can beat the probe's rate.
"""

from __future__ import annotations

import functools

#: Kernel name the trace reduction looks for.
NAME = "bench_exp_probe"
GRID, ROWS, COLS, ITERS = 32, 256, 512, 512
EXPS = GRID * ROWS * COLS * ITERS


@functools.lru_cache(maxsize=None)
def _program(grid: int, iters: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = lax.fori_loop(0, iters,
                                   lambda i, v: jnp.exp(v * -0.5),
                                   x_ref[...])

    call = pl.pallas_call(
        kernel, grid=(grid,),
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * ROWS, COLS), jnp.float32),
        interpret=interpret, name=NAME)
    return jax.jit(call), jnp.full((grid * ROWS, COLS), 0.5, jnp.float32)


def run() -> None:
    """Launch the probe three times (inside a traced region).  Off the
    chip it runs interpreted at one step, to rehearse the path only."""
    import jax

    on_chip = jax.default_backend() == "tpu"
    fn, x = _program(GRID if on_chip else 1, ITERS if on_chip else 1,
                     not on_chip)
    for _ in range(3):
        jax.block_until_ready(fn(x))
