"""Cross-client verification aggregator.

`BatchingVerifier` is a drop-in for the `verifier=` seam of
`crypto/batch.verify_generic`: it serves a caller's ed25519 column batch
by parking it as ONE row in a shared `parallel.planner.LaneFeed`, so
commit verifications issued by many concurrent clients fold into one
lane-packed planner dispatch (the breaker + host-fallback guard applies
unchanged).  The aggregation is transparent to verdict semantics by
construction: `ValidatorSet.verify_commit` et al. keep doing their own
structural checks and quorum tallies over the returned per-lane verdicts
— only the signature primitive is shared.

Anything that is not an ed25519 column batch (secp256k1, multisig, the
odd structurally-broken item) delegates to the process-default
BatchVerifier, exactly as a `verifier=None` call would resolve it.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from tendermint_tpu.parallel.planner import LaneFeed


class BatchingVerifier:
    """verify_generic-compatible verifier backed by a shared LaneFeed."""

    # the feed takes rows of bytes; said here so that __getattr__ below does
    # not answer with the default verifier's
    column_form = False

    def __init__(self, feed: LaneFeed, result_timeout: Optional[float] = 60.0):
        self._feed = feed
        self._timeout = result_timeout

    def verify_ed25519_raw(
        self,
        pubs: Sequence[bytes],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
    ) -> np.ndarray:
        n = len(pubs)
        if n == 0:
            return np.zeros((0,), dtype=bool)
        # powers/total are placeholders: the caller owns the quorum math,
        # the feed only has to return per-lane verdicts in row order
        ticket = self._feed.submit(list(zip(pubs, msgs, sigs)), [1] * n, n)
        return ticket.result(self._timeout).ok

    def verify_ed25519(self, items) -> np.ndarray:
        return self.verify_ed25519_raw(
            [it.pubkey for it in items],
            [it.msg for it in items],
            [it.sig for it in items],
        )

    def __getattr__(self, name):
        from tendermint_tpu.crypto.batch import get_batch_verifier

        return getattr(get_batch_verifier(), name)
