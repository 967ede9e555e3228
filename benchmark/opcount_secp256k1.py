"""Operations and bytes the secp256k1 ECDSA kernel's work needs, counted from
the structure of ``ops/secp256k1_pallas`` (``ladder_math``, ``pt_add``,
``_ladder_kernel``) and kept with the yardstick, like ``benchmark/opcount.py``
for ed25519, so that a later PR cannot move a rate by recounting.  Work is
counted for the lanes that carry a signature, never for padding.

One verification, as the kernel spends it:

  * the per-signature table [0..15]Q: 15 complete additions (the first,
    identity + Q, runs through the same law; the module's docstring says 14);
  * 64 windows, each 4 doublings (the complete law doubles: 256 in all, the
    first window's four on the identity among them) and 2 additions, one
    from the constant table of G and one from the table of Q;
  * a complete addition (RCB16 algorithm 7, a = 0) is 12 field
    multiplications and 2 multiplications by the small constant 3b = 21;
  * each window selects 3 coordinates from each 16-entry table by mask:
    2 x 3 x 16 products of a 20-limb row with a 0/1 row;
  * the affine-x check multiplies instead of inverting: r.Z and (r+n).Z,
    then three canonicalisations, which carry and fold but do not multiply.

A field multiplication is a 20 x 20 schoolbook over radix-2^13 u32 limbs: 400
row-products.  A multiplication by a small constant is 20.

As for ed25519, no VPU integer peak is published and none is assumed: the
multiply rate is a rate (Gmac/s), and the one published bound that applies,
HBM bandwidth (``benchmark/peaks.json``), is NOT the binding one (thousands
of multiply-accumulates a byte); its share is reported to show that.
"""

from __future__ import annotations

LIMBS = 20
ROW_PRODUCTS_PER_FE_MUL = LIMBS * LIMBS
ROW_PRODUCTS_PER_SMALL_MUL = LIMBS
WINDOWS = 64

POINT_ADDS = {
    "table": 15,
    "doublings": WINDOWS * 4,
    "window_additions": WINDOWS * 2,
}
FE_MUL_PER_POINT_ADD = 12
SMALL_MUL_PER_POINT_ADD = 2
FE_MUL_FINAL = 2  # r.Z and (r+n).Z
SELECT_ROW_PRODUCTS = WINDOWS * 2 * 3 * 16 * LIMBS


def secp256k1_fe_muls() -> int:
    """Full field multiplications one verification spends."""
    return sum(POINT_ADDS.values()) * FE_MUL_PER_POINT_ADD + FE_MUL_FINAL


def secp256k1_row_products(lanes: float) -> float:
    """u32 multiply-accumulates that ``lanes`` signatures need."""
    adds = sum(POINT_ADDS.values())
    per_lane = (secp256k1_fe_muls() * ROW_PRODUCTS_PER_FE_MUL
                + adds * SMALL_MUL_PER_POINT_ADD * ROW_PRODUCTS_PER_SMALL_MUL
                + SELECT_ROW_PRODUCTS)
    return lanes * per_lane


def secp256k1_bytes(lanes: float) -> float:
    """Bytes that have to cross HBM for ``lanes`` signatures, as the kernel
    reads them: the key's two coordinates and r and r+n as limbs (4 x 20
    u32), the two scalars as 64 window digits each (2 x 64 u32), the
    r+n-is-below-p flag and the verdict word.  The constant table of G is
    shared by the batch and the turn from lane-major to limb-major is the
    implementation's, not the algorithm's: neither is counted."""
    return lanes * (4 * LIMBS * 4 + 2 * WINDOWS * 4 + 4 + 4)


FUNCTIONS = {
    "secp256k1_row_products": secp256k1_row_products,
    "secp256k1_bytes": secp256k1_bytes,
}
