"""tendermint_tpu — a TPU-native Byzantine-fault-tolerant state machine replication framework.

A from-scratch re-design of Tendermint Core (reference: tendermint v0.26.2, Go) for TPU
hardware: the BFT control plane (consensus rounds, gossip, WAL, mempool) runs on host in
asyncio Python, while the compute-dense data plane — Ed25519/secp256k1 signature
verification, SHA hashing, Merkle trees — is batched onto TPU through JAX/Pallas kernels
behind an explicit ``BatchVerifier`` boundary (``tendermint_tpu.crypto.batch``).

Layer map (mirrors reference layer map, see SURVEY.md §1):

  cmd/        CLI entrypoints
  rpc/        JSON-RPC / WebSocket API
  node/       composition root
  consensus/  BFT state machine + gossip reactor + WAL
  blockchain/ fast sync (batched multi-height commit verification — the TPU payoff)
  mempool/ evidence/  tx + evidence pools
  state/      block execution, stores, validation
  abci/ proxy/  application interface (3 logical connections)
  types/      Block, Vote, Commit, ValidatorSet, VoteSet, PartSet, EventBus
  crypto/     host crypto: keys, merkle, multisig + the BatchVerifier boundary
  ops/        TPU kernels: ed25519 batch verify, field/curve arithmetic, hashing
  parallel/   device-mesh sharding of verification batches (pjit/shard_map)
  p2p/        authenticated-encrypted multiplexed peer transport
  libs/       runtime substrate: services, db, wal files, pubsub, bitarray
"""

import os as _os
import sys as _sys

from tendermint_tpu.version import __version__  # noqa: F401


def _place_compile_cache() -> str:
    """The ONE place the persistent XLA compile cache is placed: where
    JAX_COMPILATION_CACHE_DIR is set it wins and nothing is set in code;
    otherwise the fixed ``<checkout>/.jax_cache`` (the directory is part of
    the cache key, so it is never built from a temp name, a pid or the
    time).  Runs at package import, before any ``import jax`` of ours; a
    jax that the embedding program imported first is told directly."""
    path = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        )
        _os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        if "jax" in _sys.modules:
            _sys.modules["jax"].config.update(
                "jax_compilation_cache_dir", path)
    return path


_place_compile_cache()
