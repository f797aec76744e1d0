"""Input-precision tiers for the Flash-SD-KDE kernels.

The paper's speedup is "make the hot loop tensor-core matmuls"; on TPU the
MXU runs bf16×bf16→f32 at full rate while f32×f32 costs multiple passes
through the systolic array.  SD-KDE's statistical guarantees survive
reduced-precision *pairwise distances* as long as the sensitive scalar work
stays f32, so the kernels expose three operand tiers:

  * ``f32``    — operands as given, multiplied at
                 ``lax.Precision.HIGHEST`` (full f32: on a TPU the default
                 f32 matmul precision is a single bf16 pass, so the tier
                 asks for f32 explicitly);
  * ``bf16``   — Gram / φ@[X|1] operands cast to bfloat16 (~1e-2 relative
                 on the densities, full MXU rate, half the operand HBM
                 traffic and VMEM footprint);
  * ``bf16x2`` — split-hi–lo compensated bf16: each f32 operand A becomes
                 ``A_hi = bf16(A)`` and ``A_lo = bf16(A − A_hi)``, and each
                 GEMM runs as the four-product sum
                 ``A_hi·B_hi + A_hi·B_lo + A_lo·B_hi + A_lo·B_lo``.
                 ~16 mantissa bits → within 1e-4 of the f32 reference at 4×
                 the bf16 GEMM count — the same family as XLA's own
                 f32-as-bf16 emulation (``BF16_3X``/``BF16_6X`` passes),
                 sitting between them, and still cheaper than the
                 multi-pass lowering the f32 tier's HIGHEST GEMM costs.

Invariant across every tier (tested in tests/test_precision_autotune.py):
squared norms, ``sq = ‖y‖² + ‖x‖² − 2g``, the exponential, the Laplace
correction, and all accumulators stay f32 — only GEMM *operands* shrink.
One subtlety makes the tiers well-behaved: at a reduced tier the f32 norms
are computed from the *tier-cast* operands (ŷ = cast(y)), so
``sq = ‖ŷ‖² + ‖x̂‖² − 2·ŷ·x̂ = ‖ŷ − x̂‖²`` is an exact nonnegative squared
distance of slightly perturbed points — precision loss acts as a data
perturbation (the regime SD-KDE's guarantees tolerate) instead of a
catastrophic-cancellation error in the exponent.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

PRECISIONS = ("f32", "bf16", "bf16x2")
Precision = str  # one of PRECISIONS; plain str keeps it jit-static-friendly


def validate(precision: Precision) -> Precision:
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision tier {precision!r} (choose from {PRECISIONS})"
        )
    return precision


def operand_bytes(precision: Precision) -> int:
    """Effective bytes/element of GEMM operand storage and HBM streaming.

    bf16x2 stores *two* bf16 planes per operand, so its footprint matches
    f32 — the win there is MXU rate, not bytes.
    """
    validate(precision)
    return {"f32": 4, "bf16": 2, "bf16x2": 4}[precision]


def gram_products(precision: Precision) -> int:
    """MXU product count per logical GEMM (bf16x2 runs the 4-product sum)."""
    validate(precision)
    return 4 if precision == "bf16x2" else 1


def split_hi_lo(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compensated split: f32 ``x`` → (bf16 hi, bf16 lo) with x ≈ hi + lo."""
    x32 = x.astype(jnp.float32)
    hi = x32.astype(jnp.bfloat16)
    lo = (x32 - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def cast_operand(
    x: jnp.ndarray, precision: Precision
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(hi, lo) GEMM operand pair for a tier; ``lo`` is None below bf16x2.

    ``f32`` keeps the array's own dtype (bf16 *data* stays bf16, matching
    the seed kernels' behavior of computing in whatever the caller supplies).
    """
    validate(precision)
    if precision == "f32":
        return x, None
    if precision == "bf16":
        return x.astype(jnp.bfloat16), None
    return split_hi_lo(x)


def reconstruct(hi: jnp.ndarray, lo: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The f32 points a (hi, lo) operand pair actually represents."""
    r = hi.astype(jnp.float32)
    if lo is not None:
        r = r + lo.astype(jnp.float32)
    return r


def dot_f32(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """GEMM with f32 accumulation; f32 operands multiply at full f32.

    Without an explicit precision, an f32×f32 product on a TPU may run as
    one bf16 pass (XLA's default), which turns ``‖y‖²+‖x‖²−2y·x`` into
    cancellation noise.  bf16 operands (the reduced tiers) are exact in
    one pass and keep the default.
    """
    f32 = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=lax.Precision.HIGHEST if f32 else None)


def gram_compensated(
    a_hi: jnp.ndarray, a_lo: jnp.ndarray,
    b_hi: jnp.ndarray, b_lo: jnp.ndarray,
) -> jnp.ndarray:
    """Four-product compensated GEMM with f32 accumulation (bf16x2 tier).

    Keeping the ``a_lo·b_lo`` term makes the result the exact (to f32
    rounding) Gram of the reconstructed operands ``(a_hi+a_lo)·(b_hi+b_lo)``
    — required for ``sq = ‖ŷ−x̂‖²`` to stay a true squared distance when
    norms are computed from the same reconstruction (see module docstring).
    """
    g = dot_f32(a_hi, b_hi)
    g = g + dot_f32(a_hi, b_lo)
    g = g + dot_f32(a_lo, b_hi)
    g = g + dot_f32(a_lo, b_lo)
    return g


def weighted_accum(phi: jnp.ndarray, w_hi: jnp.ndarray,
                   w_lo: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The φ@[X|1] accumulator GEMM at the tier implied by the operands.

    ``phi`` arrives f32 (it is exp output); the weight matrix's dtype (plus
    the presence of a lo plane) selects the tier, so kernel bodies need no
    explicit precision flag.
    """
    if w_lo is not None:                       # bf16x2: split φ too
        p_hi, p_lo = split_hi_lo(phi)
        return gram_compensated(p_hi, p_lo, w_hi, w_lo)
    if w_hi.dtype == jnp.bfloat16:             # bf16: both operands bf16
        return dot_f32(phi.astype(jnp.bfloat16), w_hi)
    return dot_f32(phi, w_hi.astype(jnp.float32))


__all__ = [
    "PRECISIONS", "Precision", "validate", "operand_bytes", "gram_products",
    "split_hi_lo", "cast_operand", "reconstruct", "dot_f32",
    "gram_compensated", "weighted_accum",
]
