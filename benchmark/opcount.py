"""Operations and bytes the verify kernels' work needs, from shapes alone.

A model kept with the yardstick (copied from PERF.md's "Model" section; the
kernels' own counters are in ``ops/fe_common.carry_cost_model``), so that a
later PR cannot move a rate by recounting.  Work is counted for the lanes
that carry a signature, never for padding: a kernel that pads less does not
do more.

No VPU integer peak is published for any TPU, so none is assumed: the
multiply rate is reported as a rate (``Gmac/s``), not as a share of a peak.
The one published bound that applies is HBM bandwidth
(``benchmark/peaks.json``); it is NOT the binding bound of this kernel,
whose arithmetic intensity is thousands of multiply-accumulates a byte, and
the share is reported to show exactly that.
"""

from __future__ import annotations

# field multiplications (or equivalents) one ed25519 verification spends in
# ``ops/ed25519_pallas``: the 16-entry table, 64 windows of 4 doublings and
# 2 additions, constant-time selects, the final inversion, the encoding
ED25519_FE_MUL = {
    "table": 120,
    "ladder": 64 * 48,
    "selects": 360,
    "inversion": 265,
    "encode": 8,
}
# one field multiplication: a 20 x 20 schoolbook over radix-2^13 u32 limbs
ROW_PRODUCTS_PER_FE_MUL = 20 * 20


def ed25519_row_products(lanes: float) -> float:
    """u32 multiply-accumulates that ``lanes`` signatures need."""
    return lanes * sum(ED25519_FE_MUL.values()) * ROW_PRODUCTS_PER_FE_MUL


def ed25519_bytes(lanes: float, varying_words: int = 2) -> float:
    """Bytes that have to cross HBM for ``lanes`` signatures: the signature
    (64), the public key's words for the hash (32), its decompressed limbs
    as the ladder reads them (2 x 20 u32), the message words that vary
    across the batch, and the verdict word.  The message template is shared
    by the batch and the intermediate between prologue and ladder is the
    implementation's, not the algorithm's: neither is counted."""
    return lanes * (64 + 32 + 2 * 20 * 4 + 4 * varying_words + 4)


FUNCTIONS = {
    "ed25519_row_products": ed25519_row_products,
    "ed25519_bytes": ed25519_bytes,
}
