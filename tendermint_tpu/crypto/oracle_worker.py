"""The host oracle as the guard's audit calls it, and the child process that
runs it beside a node: ``python -m tendermint_tpu.crypto.oracle_worker``.

``verify_rows`` is the one place the audit's oracle calls are written: the
guard runs it inline on small samples, ``crypto/oracle_pool.py``'s children
run it on large ones, so the Go accept set, the OpenSSL fast path and the
``_verify_pure`` fallback are the same code either way.

The child reads length-prefixed request frames from its stdin and writes one
reply frame each to its stdout, in order, until its stdin reaches EOF: it
cannot outlive the process that holds the other end.  It imports the host
crypto only (no jax, no numpy) and never touches a device.

    request  <I len> <Q id> <B algo> <I n> <3n x I lengths> pub msg sig ...
    reply    <I len> <Q id> <B status> <n x B verdict>      status 0 = ok
"""

from __future__ import annotations

import struct
from itertools import chain
from typing import List, Sequence, Tuple

from tendermint_tpu.crypto import ed25519 as _ed

Row = Tuple[bytes, bytes, bytes]  # (pubkey, msg, sig) as the guard's caller gave them

ALGOS = ("ed25519", "secp256k1")
_HEAD = struct.Struct("<QBI")
_LEN = struct.Struct("<I")
_REPLY = struct.Struct("<QB")
# a frame longer than this is not one of ours: the stream is out of step
MAX_FRAME = 1 << 30


def verify_rows(algo: str, rows: Sequence[Row]) -> List[bool]:
    """The host oracle's verdict on each row: ``ed25519.verify(pub, msg,
    sig)``, or for secp256k1 ``verify(pub, sha256(msg), sig)`` with the
    SHA-256 premix of secp256k1.go:140."""
    if algo == "ed25519":
        verify = _ed.verify
        return [bool(verify(p, m, s)) for p, m, s in rows]
    if algo == "secp256k1":
        from tendermint_tpu.crypto import secp256k1 as _secp
        from tendermint_tpu.crypto.hashing import sha256

        verify = _secp.verify
        return [bool(verify(p, sha256(m), s)) for p, m, s in rows]
    raise ValueError(f"no host oracle for algo {algo!r}")


def encode_request(req_id: int, algo: str, rows: Sequence[Row]) -> bytes:
    flat = list(chain.from_iterable(rows))
    body = b"".join([
        _HEAD.pack(req_id, ALGOS.index(algo), len(rows)),
        struct.pack(f"<{len(flat)}I", *map(len, flat)),
        *flat,
    ])
    return _LEN.pack(len(body)) + body


def decode_request(body: bytes) -> Tuple[int, str, List[Row]]:
    req_id, algo, n = _HEAD.unpack_from(body)
    lens = struct.unpack_from(f"<{3 * n}I", body, _HEAD.size)
    at = _HEAD.size + 12 * n
    flat = []
    for ln in lens:
        flat.append(body[at:at + ln])
        at += ln
    if at != len(body):
        raise ValueError("request frame does not add up")
    return req_id, ALGOS[algo], list(zip(flat[0::3], flat[1::3], flat[2::3]))


def encode_reply(req_id: int, verdicts: Sequence[bool], status: int = 0) -> bytes:
    body = _REPLY.pack(req_id, status) + bytes(verdicts)
    return _LEN.pack(len(body)) + body


def decode_reply(body: bytes) -> Tuple[int, int, List[bool]]:
    req_id, status = _REPLY.unpack_from(body)
    return req_id, status, [b == 1 for b in body[_REPLY.size:]]


def _read_exact(stream, n: int) -> bytes:
    buf = stream.read(n)
    return buf if buf is not None and len(buf) == n else b""


def serve(stdin, stdout) -> None:
    """Answer frames until ``stdin`` ends."""
    while True:
        head = _read_exact(stdin, _LEN.size)
        if not head:
            return
        (size,) = _LEN.unpack(head)
        body = _read_exact(stdin, size) if size <= MAX_FRAME else b""
        if not body:
            return
        req_id = _HEAD.unpack_from(body)[0] if size >= _HEAD.size else 0
        try:
            req_id, algo, rows = decode_request(body)
            reply = encode_reply(req_id, verify_rows(algo, rows))
        except Exception:
            # the parent verifies these lanes itself and meets the same
            # exception where it can be raised to the caller
            import traceback

            traceback.print_exc()
            reply = encode_reply(req_id, (), status=1)
        stdout.write(reply)
        stdout.flush()


def main() -> None:
    import signal
    import sys

    # a terminal's ^C goes to the whole process group: the node decides when
    # it is done verifying, and closes our stdin then
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    # both key types' oracles ready before the first frame
    from tendermint_tpu.crypto import hashing, secp256k1  # noqa: F401

    try:
        serve(sys.stdin.buffer, sys.stdout.buffer)
    except BrokenPipeError:
        pass


if __name__ == "__main__":
    main()
