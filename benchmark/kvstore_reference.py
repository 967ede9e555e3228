"""The plain reference of the deployment ``fastsync-64v-full``: upstream's
example kvstore as a state machine, and the three hashes a block of
transactions puts into a chain's headers.

What is decided here is decided by this file's own arithmetic over plain
bytes and integers; nothing of ``tendermint_tpu`` is imported.  The merkle
tree is ``chaingen.merkle_root`` (RFC 6962 prefixes, split at the largest
power of two below n), itself written out for the benchmark.

The app (v0.26.2 ``abci/example/kvstore/kvstore.go``, as ISSUE 43 states
it; the Go source is not in the sandbox, so the rule is also listed under
the configuration's ``assumed``):

* DeliverTx: a tx is ``key=value``, split at its first ``=``; a tx without
  ``=`` is key and value alike; a later write of a key wins; ``size`` counts
  delivered txs, not keys.  Every tx is accepted (code 0, no data).
* Commit: the app hash is ``make([]byte, 8)`` after
  ``binary.PutVarint(appHash, size)``: the zigzag of ``size`` written seven
  bits a byte from the low end, 0x80 on every byte but the last, zeros
  behind.  No pass over the state.
* Query: the value last written under the key, empty where there is none.

The block (``types/block.go``, ``types/part_set.go``, ``types/results.go``
as this repo's ``types/`` documents them):

* data hash: the merkle root over the block's txs, each a leaf as it is;
* part set: the encoded block cut into 65,536-byte parts, ``total`` their
  number (at least one), ``hash`` the merkle root over the parts;
* last-results hash: the merkle root over one ``uvarint(code) ++
  uvarint(len(data)) ++ data`` a DeliverTx result; 1,000 accepted txs are
  1,000 leaves of two zero bytes.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from benchmark.chaingen import merkle_root

PART_SIZE = 65536
APP_HASH_BYTES = 8
OK_RESULT = b"\x00\x00"  # uvarint(0) for the code, uvarint(0) for no data


def put_varint(size: int) -> bytes:
    """``binary.PutVarint`` into a zeroed 8-byte buffer."""
    ux = size * 2 if size >= 0 else -size * 2 - 1  # zigzag
    out = []
    while ux >= 128:
        out.append(ux % 128 + 128)
        ux //= 128
    out.append(ux)
    if len(out) > APP_HASH_BYTES:
        raise OverflowError(f"the varint of {size} needs {len(out)} bytes")
    return bytes(out + [0] * (APP_HASH_BYTES - len(out)))


class KVStore:
    """The app's state machine."""

    def __init__(self):
        self.state: Dict[bytes, bytes] = {}
        self.size = 0

    def deliver(self, tx: bytes) -> None:
        at = tx.find(b"=")
        key, value = (tx, tx) if at < 0 else (tx[:at], tx[at + 1:])
        self.state[key] = value
        self.size += 1

    def app_hash(self) -> bytes:
        return put_varint(self.size)

    def query(self, key: bytes) -> bytes:
        return self.state.get(key, b"")


def data_hash(txs: Sequence[bytes]) -> bytes:
    return merkle_root(list(txs))


def results_hash(results: int) -> bytes:
    """Of a block of ``results`` accepted txs.  (Block 1's header, which
    follows no block, carries no hash at all.)"""
    return merkle_root([OK_RESULT] * results)


def part_set_header(block_bytes: bytes) -> Tuple[int, bytes]:
    """(total, hash) of the encoded block's part set."""
    total = max(1, -(-len(block_bytes) // PART_SIZE))
    parts = [block_bytes[i * PART_SIZE: (i + 1) * PART_SIZE]
             for i in range(total)]
    return total, merkle_root(parts)


def _uvarint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, at
        shift += 7


def block_bytes(response: bytes) -> bytes:
    """The encoded block inside one ``BlockResponseMessage`` as a peer
    sends it: a varint tag, a varint length, the block."""
    _tag, at = _uvarint(response, 0)
    n, at = _uvarint(response, at)
    if at + n != len(response):
        raise ValueError("a block response with bytes left over")
    return response[at: at + n]
