"""Flash-SD-KDE Pallas kernels, their wrappers (``ops``) and tuning.

``interpret_mode`` is the one place the Pallas execution mode is decided:
every ``pallas_call`` in this package passes its ``interpret`` argument
through it (via ``launch_interpret``), so ``None`` (the default everywhere
above the kernels) means "compiled on a TPU, interpreted elsewhere".  ``compiler_params`` is the
one Mosaic setting every kernel shares.
"""

from __future__ import annotations

from typing import Optional

#: Scoped VMEM a kernel may use.  A v5e core has 128 MiB of VMEM, but
#: Mosaic's default scope is 16 MiB.  The tile model (``tuning.VMEM_BUDGET``)
#: counts operand tiles, one φ tile and the accumulator; the compiled body
#: also holds the Gram tile and, at bf16x2, the partial products, so a
#: modeled-feasible 512 x 4096 bf16x2 tile needs 16.7 MiB (compiled for a
#: described v5e, tests/test_tpu_compile.py).
VMEM_LIMIT_BYTES = 64 * 2**20


def compiler_params():
    """Mosaic compiler parameters for every kernel in this package."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``None`` derives it from the platform: compiled Mosaic on a TPU,
    the Pallas interpreter on any other backend (CPU validation).  An
    explicit bool wins.
    """
    import jax

    return (jax.default_backend() != "tpu" if interpret is None
            else bool(interpret))


def launch_interpret(interpret: Optional[bool]) -> bool:
    """``interpret_mode`` for one ``pallas_call``, counted.

    Called at trace time, once per kernel program; the
    ``kernels.pallas_traces`` counter records which mode each traced kernel
    took, so a run can assert that nothing ran interpreted.
    """
    from repro import obs

    mode = interpret_mode(interpret)
    obs.counter("kernels.pallas_traces",
                "Pallas kernel programs traced, by execution mode",
                labels={"mode": "interpret" if mode else "compiled"}).inc()
    return mode
