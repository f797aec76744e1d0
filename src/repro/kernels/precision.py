"""Input-precision tiers for the Flash-SD-KDE kernels.

The paper's speedup is "make the hot loop tensor-core matmuls"; on TPU the
MXU runs bf16×bf16→f32 at full rate while f32×f32 costs multiple passes
through the systolic array.  SD-KDE's statistical guarantees survive
reduced-precision *pairwise distances* as long as the sensitive scalar work
stays f32, so the kernels expose three operand tiers:

  * ``f32``    — operands as given, multiplied at
                 ``lax.Precision.HIGHEST`` (full f32: on a TPU the default
                 f32 matmul precision is a single bf16 pass, so the tier
                 asks for f32 explicitly) in the dense kernels, and as
                 the same split products packed (below) in the pruned
                 ones;
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

Packed f32 GEMMs.  ``HIGHEST`` is six bf16 passes over a three-way split
(hi·hi, hi·mid, mid·hi, mid·mid, hi·lo, lo·hi — XLA's ``BF16_6X``), and each
pass runs padded to the MXU's 128 × 128: a K=16 distance Gram fills 1/8 of
its depth and the (d+1)-wide score numerator 17/128 of its width.  At the
f32 tier the pruned kernels issue the same products explicitly, stacked
into the dimension the MXU pads: ``ceil(6·d/128)`` bf16 passes for the
Gram (one up to d = 21) and one for the numerator up to d = 42:

  * Gram — row operand ``[a3, a1, a2, a2, a1, a1]`` (m × 6d, built once by
    :func:`pack_rows`) against ``[b1; b3; b2; b1; b2; b1]`` (6d × n), sliced
    in the kernel from the column planes ``[x1ᵀ; x2ᵀ; x3ᵀ; 1; 0…]``
    (:func:`column_planes`): the six products smallest first, so f32
    accumulation adds ``a1·b1`` last;
  * numerator — the same column planes against φ's three planes stacked
    along rows (3·bm × bn): all nine products and the ones row's row sum
    in one GEMM, reduced to ``(·, d+1)`` by :func:`reduce_planes`.

Every product of two bf16 planes is exact in f32 and summed in f32, so the
packed Gram errs as an f32 dot does (tests/test_packed_gemm.py).

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

import jax
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


#: Rows of one bf16 sublane tile: the column planes are padded to it.
_BF16_ROWS = 16


def packs(precision: Precision) -> bool:
    """Whether the pruned kernels run this tier's GEMMs packed: the f32
    tier, at every ``d``."""
    return precision == "f32"


def split3(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """f32 ``x`` → three bf16 planes with ``x == p1 + p2 + p3``: each plane
    takes the next 8 mantissa bits of the residual, so the split is exact.

    For XLA programs: each plane is rounded by ``lax.reduce_precision``.
    Inside a fused TPU program XLA may keep an f32 → bf16 → f32 round trip
    in f32 (excess precision), which leaves the lower planes zero: a jitted
    split by ``astype`` gave a Gram exactly as wrong as one bf16 pass on a
    v5e.  Kernel bodies use :func:`_split3_in_kernel`."""
    x32 = x.astype(jnp.float32)
    p1 = lax.reduce_precision(x32, exponent_bits=8, mantissa_bits=7)
    r = x32 - p1
    p2 = lax.reduce_precision(r, exponent_bits=8, mantissa_bits=7)
    return tuple(p.astype(jnp.bfloat16) for p in (p1, p2, r - p2))


def _split3_in_kernel(x: jnp.ndarray):
    """:func:`split3` in a Pallas kernel body: Mosaic has no
    ``reduce_precision`` and rounds every conversion it is given."""
    p1 = x.astype(jnp.bfloat16)
    r = x - p1.astype(jnp.float32)
    p2 = r.astype(jnp.bfloat16)
    return p1, p2, (r - p2.astype(jnp.float32)).astype(jnp.bfloat16)


def plane_rows(d: int) -> int:
    """Rows of :func:`column_planes`: three planes and the ones row,
    padded to a bf16 sublane tile."""
    return -(-(3 * d + 1) // _BF16_ROWS) * _BF16_ROWS


#: HIGHEST's six products as (row plane, column plane), smallest first.
_PRODUCTS = ((2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))


@jax.jit
def pack_rows(a: jnp.ndarray) -> jnp.ndarray:
    """(m, d) f32 → (m, 6d) bf16 ``[a3, a1, a2, a2, a1, a1]``: the Gram's
    row operand, one block per product of :func:`gram_packed`."""
    planes = split3(a)
    return jnp.concatenate([planes[i] for i, _ in _PRODUCTS], axis=1)


@jax.jit
def column_planes(x: jnp.ndarray) -> jnp.ndarray:
    """(n, d) f32 → (plane_rows(d), n) bf16 ``[x1ᵀ; x2ᵀ; x3ᵀ; 1; 0…]``, lane
    axis = the streamed columns: the Gram's column planes and the
    numerator's [X | 1] weights in one array."""
    n, d = x.shape
    x1, x2, x3 = split3(x)
    pad = plane_rows(d) - 3 * d - 1
    return jnp.concatenate(
        [x1.T, x2.T, x3.T, jnp.ones((1, n), jnp.bfloat16),
         jnp.zeros((pad, n), jnp.bfloat16)], axis=0)


def gram_packed(rows: jnp.ndarray, planes: jnp.ndarray) -> jnp.ndarray:
    """HIGHEST's six products of one Gram tile in one bf16 GEMM.

    ``rows`` is a (bm, 6d) tile of :func:`pack_rows`, ``planes`` a
    (plane_rows(d), bn) tile of :func:`column_planes`; the right-hand side
    ``[b1; b3; b2; b1; b2; b1]`` is cut from the planes along sublanes.
    """
    d = rows.shape[1] // 6
    rhs = jnp.concatenate([planes[j * d:(j + 1) * d] for _, j in _PRODUCTS],
                          axis=0)
    return jnp.dot(rows, rhs, preferred_element_type=jnp.float32)


def weighted_accum_packed(phi: jnp.ndarray,
                          planes: jnp.ndarray) -> jnp.ndarray:
    """φ@[X|1] as one bf16 GEMM, transposed: a (R, bn) tile of
    :func:`column_planes` against (bm, bn) f32 φ → (R, bm) f32, every
    product of x's and φ's three planes plus φ's row sum (the ones row);
    :func:`reduce_planes` folds it.  Plane sums here and there add the
    smallest first."""
    bm = phi.shape[0]
    stacked = jnp.concatenate(_split3_in_kernel(phi), axis=0)  # (3bm, bn)
    acc = lax.dot_general(planes, stacked, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return (acc[:, 2 * bm:] + acc[:, bm:2 * bm]) + acc[:, :bm]


def reduce_planes(acc: jnp.ndarray, d: int) -> jnp.ndarray:
    """(m, plane_rows(d)) accumulator (:func:`weighted_accum_packed`,
    transposed back) → (m, d+1) ``[Σφx | Σφ]``."""
    s1 = (acc[:, 2 * d:3 * d] + acc[:, d:2 * d]) + acc[:, :d]
    return jnp.concatenate([s1, acc[:, 3 * d:3 * d + 1]], axis=1)


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
    "gram_compensated", "weighted_accum", "packs", "split3",
    "plane_rows", "pack_rows", "column_planes", "gram_packed",
    "weighted_accum_packed", "reduce_planes",
]
