"""Host micro-benchmarks mirroring the reference's bench harnesses — the
host-path numbers that explain where the fast-sync/consensus millisecond goes.

  codec      — block/valset/vote encode+decode round-trips
               (ref: benchmarks/codec_test.go:30 BenchmarkEncode*/Decode*)
  wal        — WAL record decode throughput at entry sizes 512 B -> 1 MB
               (ref: consensus/wal_test.go:163-182 BenchmarkWalDecode*)
  mempool    — reap_max_bytes_max_gas over a full pool
               (ref: mempool/bench_test.go:11 BenchmarkReap)
  proposal   — proposal sign + verify through FilePV
               (ref: types/proposal_test.go:77-93 BenchmarkProposal*)

Prints one JSON line per benchmark:
  {"metric": "...", "value": N, "unit": "..."}
"""

import json
import os
import struct
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(metric: str, value: float, unit: str, **extra):
    line = {"metric": metric, "value": round(value, 3), "unit": unit}
    line.update(extra)
    print(json.dumps(line), flush=True)


def _time_per_op(fn, min_s: float = 0.4):
    """Median-ish ops/s: run batches until min_s of wall clock."""
    fn()  # warm
    n, t = 0, 0.0
    t0 = time.perf_counter()
    while t < min_s:
        fn()
        n += 1
        t = time.perf_counter() - t0
    return t / n


def bench_codec():
    from tendermint_tpu.testutil.chain import build_chain

    fx = build_chain(n_vals=64, n_heights=4, chain_id="bench-codec")
    block = fx.block_store.load_block(3)
    raw_block = block.marshal()
    valset = fx.state.validators
    raw_valset = valset.marshal()
    vote = block.last_commit.precommits[0]

    from tendermint_tpu.types import Block
    from tendermint_tpu.types.validator_set import ValidatorSet

    def _fresh_marshal():
        # bypass the memo caches: measure the encoders, not the dict hits
        valset._marshal_cache = None
        valset.marshal()

    def _fresh_block_marshal():
        block._marshal_cache = None
        block.marshal()

    _emit("codec_block_encode_64v",
          _time_per_op(_fresh_block_marshal) * 1e6, "us", bytes=len(raw_block))
    _emit("codec_block_decode_64v",
          _time_per_op(lambda: Block.unmarshal(raw_block)) * 1e6, "us")
    _emit("codec_valset_encode_64v", _time_per_op(_fresh_marshal) * 1e6, "us",
          bytes=len(raw_valset))
    _emit("codec_valset_decode_64v",
          _time_per_op(lambda: ValidatorSet.unmarshal(raw_valset)) * 1e6, "us")
    _emit("codec_vote_signbytes",
          _time_per_op(lambda: vote.sign_bytes("bench-codec")) * 1e6, "us")


def bench_wal(tmp_dir: str):
    from tendermint_tpu.consensus.messages import BlockPartMessage, encode_msg
    from tendermint_tpu.consensus.wal import WAL, TimedWALMessage
    from tendermint_tpu.crypto.merkle import SimpleProof
    from tendermint_tpu.encoding.codec import encode_uvarint
    from tendermint_tpu.types.part_set import Part

    # entry ceiling is MAX_MSG_SIZE_BYTES (1 MB, ref maxMsgSizeBytes) —
    # the top size stays under it after framing
    for size in (512, 4096, 65536, 524288):
        msg = BlockPartMessage(
            height=1, round=0,
            part=Part(index=0, bytes_=os.urandom(size),
                      proof=SimpleProof(total=1, index=0, leaf_hash=b"\0" * 32,
                                        aunts=[])),
        )
        payload = TimedWALMessage(1_700_000_000_000_000_000, msg).marshal()
        rec = (struct.pack("<I", zlib.crc32(payload))
               + encode_uvarint(len(payload)) + payload)
        n_recs = max(4, (4 << 20) // len(rec))
        path = os.path.join(tmp_dir, f"wal-{size}")
        with open(path, "wb") as f:
            f.write(rec * n_recs)
        wal = WAL(path)
        try:
            t0 = time.perf_counter()
            n = sum(1 for _ in wal.iter_all())
            dt = time.perf_counter() - t0
        finally:
            wal.group.close()
        assert n == n_recs
        _emit(f"wal_decode_{size}B", n_recs * len(rec) / dt / 1e6, "MB/s",
              records_per_s=round(n_recs / dt))


def bench_mempool():
    from tendermint_tpu.abci.examples.kvstore import KVStoreApp
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.proxy.app_conn import LocalClientCreator, MultiAppConn

    conn = MultiAppConn(LocalClientCreator(KVStoreApp()))
    conn.start()
    mp = Mempool(conn.mempool, recheck=False)
    n_txs = 5000
    t0 = time.perf_counter()
    for i in range(n_txs):
        mp.check_tx(b"k%d=v%d" % (i, i))
    checktx_s = time.perf_counter() - t0
    assert mp.size() == n_txs
    _emit("mempool_checktx", n_txs / checktx_s, "tx/s")
    per = _time_per_op(lambda: mp.reap_max_bytes_max_gas(-1, -1))
    _emit(f"mempool_reap_{n_txs}", per * 1e3, "ms",
          txs=len(mp.reap_max_bytes_max_gas(-1, -1)))


def bench_proposal(tmp_dir: str):
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types.core import BlockID, PartSetHeader
    from tendermint_tpu.types.proposal import Proposal

    pv = FilePV.generate(os.path.join(tmp_dir, "pv.json"))
    chain_id = "bench-prop"

    height = [0]

    def _sign():
        height[0] += 1
        p = Proposal(
            height=height[0], round=0,
            timestamp_ns=1_700_000_000_000_000_000,
            block_id=BlockID(b"\xcd" * 32, PartSetHeader(16, b"\xab" * 32)),
            pol_round=-1,
        )
        return pv.sign_proposal(chain_id, p)

    _emit("proposal_sign", _time_per_op(_sign) * 1e6, "us")
    signed = _sign()
    pub = pv.get_pub_key()
    sb = signed.sign_bytes(chain_id)
    assert pub.verify_bytes(sb, signed.signature)
    _emit(
        "proposal_verify",
        _time_per_op(lambda: pub.verify_bytes(sb, signed.signature)) * 1e6,
        "us",
    )


def main():
    import tempfile

    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    with tempfile.TemporaryDirectory() as tmp:
        if which in ("all", "codec"):
            bench_codec()
        if which in ("all", "wal"):
            bench_wal(tmp)
        if which in ("all", "mempool"):
            bench_mempool()
        if which in ("all", "proposal"):
            bench_proposal(tmp)


if __name__ == "__main__":
    main()
