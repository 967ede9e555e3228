"""RPC core handlers — read node state, broadcast txs
(ref: rpc/core/ routes at rpc/core/routes.go:9-41; wiring node/node.go:618).

Every handler returns JSON-able dicts.  Errors raise RPCError(code, message).
"""

from __future__ import annotations

import base64
import contextlib
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

from tendermint_tpu.abci import types as abci
from tendermint_tpu.mempool.mempool import MempoolFullError, TxInCacheError
from tendermint_tpu.types.events import EVENT_TX, TX_HASH_KEY, query_for_event


class RPCError(Exception):
    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code
        self.message = message


# broadcast_tx_* shed under overload: explicit, immediately distinguishable
# from a generic internal error so clients can back off instead of retrying
ERR_MEMPOOL_OVERLOADED = -32001


def _b64(b: bytes) -> str:
    return base64.b64encode(b).decode()


class RPCEnv:
    """The handler table; method names match the reference routes."""

    def __init__(self, node):
        self.node = node
        self._broadcast_mtx = threading.Lock()
        self._broadcast_in_flight = 0
        self.broadcast_shed: Dict[str, int] = {}
        self._feed_mtx = threading.Lock()
        self._feed = None  # lazy shared planner LaneFeed (commit verify)

    # load-shedding: broadcast_tx_* share one bounded in-flight budget; at
    # the cap new submissions fail fast with a mempool-overloaded error
    # instead of queueing unboundedly behind CheckTx / commit waits
    @contextlib.contextmanager
    def _broadcast_slot(self, route: str):
        cfg = getattr(self.node, "config", None)
        budget = getattr(cfg.rpc, "broadcast_max_in_flight", 0) if cfg else 0
        with self._broadcast_mtx:
            if budget > 0 and self._broadcast_in_flight >= budget:
                self.broadcast_shed[route] = self.broadcast_shed.get(route, 0) + 1
                m = getattr(self.node, "metrics", None)
                if m is not None:
                    m.mempool_qos_shed_total.add(1.0, (route,))
                raise RPCError(
                    ERR_MEMPOOL_OVERLOADED,
                    f"mempool overloaded: {self._broadcast_in_flight} "
                    f"broadcast_tx requests in flight (budget {budget})",
                )
            self._broadcast_in_flight += 1
        try:
            yield
        finally:
            with self._broadcast_mtx:
                self._broadcast_in_flight -= 1

    def _check_tx_guarded(self, raw: bytes, callback=None) -> None:
        """check_tx with mempool admission errors mapped to explicit RPC
        errors (a full pool is overload, a cache hit is a client dup)."""
        try:
            self.node.mempool.check_tx(raw, callback=callback)
        except MempoolFullError as e:
            raise RPCError(ERR_MEMPOOL_OVERLOADED, f"mempool overloaded: {e}")
        except TxInCacheError as e:
            raise RPCError(-32603, str(e))

    # info ------------------------------------------------------------------
    def health(self) -> dict:
        """Empty when healthy and no watchdog; with the liveness watchdog
        running it carries the compact stall summary so `curl /health` is
        enough to see a stuck chain."""
        wd = getattr(self.node, "watchdog", None)
        if wd is None:
            return {}
        return wd.status()

    def status(self) -> dict:
        return self.node.status()

    def genesis(self) -> dict:
        import json

        return {"genesis": json.loads(self.node.genesis_doc.to_json())}

    def block(self, height: Optional[int] = None) -> dict:
        bs = self.node.block_store
        h = int(height) if height else bs.height()
        meta = bs.load_block_meta(h)
        if meta is None:
            raise RPCError(-32603, f"no block for height {h}")
        block = bs.load_block(h)
        return {
            "block_meta": {
                "block_id": {
                    "hash": meta.block_id.hash.hex().upper(),
                    "parts": {
                        "total": meta.block_id.parts_header.total,
                        "hash": meta.block_id.parts_header.hash.hex().upper(),
                    },
                },
                "header": _header_json(meta.header),
            },
            "block": {
                "header": _header_json(block.header),
                "data": {"txs": [_b64(bytes(t)) for t in block.data.txs]},
                "last_commit": {
                    "block_id": {"hash": block.last_commit.block_id.hash.hex().upper()},
                    "precommits_count": sum(
                        1 for pc in block.last_commit.precommits if pc
                    ),
                },
            },
        }

    BLOCKCHAIN_INFO_LIMIT = 20  # reference blocks.go:60 const limit

    def blockchain(self, minHeight: int = 0, maxHeight: int = 0) -> dict:
        """Block metas for [minHeight, maxHeight], newest first, capped at 20
        (ref BlockchainInfo rpc/core/blocks.go:66 + filterMinMax)."""
        bs = self.node.block_store
        store_height = bs.height()
        min_h, max_h = int(minHeight), int(maxHeight)
        if min_h < 0 or max_h < 0:
            raise RPCError(-32602, "heights must be non-negative")
        if min_h == 0:
            min_h = 1
        max_h = store_height if max_h == 0 else min(store_height, max_h)
        min_h = max(min_h, max_h - self.BLOCKCHAIN_INFO_LIMIT + 1)
        if min_h > max_h:
            raise RPCError(
                -32603, f"min height {min_h} can't be greater than max height {max_h}"
            )
        metas = []
        for h in range(max_h, min_h - 1, -1):
            meta = bs.load_block_meta(h)
            if meta is None:
                continue
            metas.append(
                {
                    "block_id": {
                        "hash": meta.block_id.hash.hex().upper(),
                        "parts": {
                            "total": meta.block_id.parts_header.total,
                            "hash": meta.block_id.parts_header.hash.hex().upper(),
                        },
                    },
                    "header": _header_json(meta.header),
                }
            )
        return {"last_height": store_height, "block_metas": metas}

    def block_results(self, height: Optional[int] = None) -> dict:
        """ABCI results (DeliverTx, EndBlock) recorded for a height
        (ref BlockResults rpc/core/blocks.go:353; responses saved per height
        in the state store like state/store.go:204)."""
        from tendermint_tpu.state import store as sm_store

        bs = self.node.block_store
        h = int(height) if height else bs.height()
        if h < 1 or h > bs.height():
            raise RPCError(-32603, f"height {h} is not available")
        try:
            resp = sm_store.load_abci_responses(self.node.state_db, h)
        except Exception as e:
            raise RPCError(-32603, f"no results for height {h}: {e}")
        end_block = resp.end_block
        return {
            "height": h,
            "results": {
                "DeliverTx": [_tx_res_json(r) for r in (resp.deliver_tx or [])],
                "EndBlock": {
                    "validator_updates": [
                        {
                            "pub_key": vu.pub_key.to_json_obj()
                            if hasattr(vu.pub_key, "to_json_obj")
                            else _b64(vu.pub_key),
                            "power": vu.power,
                        }
                        for vu in (end_block.validator_updates if end_block else [])
                    ],
                    "tags": [
                        {"key": _b64(kv.key), "value": _b64(kv.value)}
                        for kv in (end_block.tags if end_block else [])
                    ],
                },
            },
        }

    def consensus_state(self) -> dict:
        """Compact live round state — the RoundStateSimple form
        (ref ConsensusState rpc/core/consensus.go:261)."""
        cs = self.node.consensus_state
        rs = cs.get_round_state()
        votes = None
        if rs.votes is not None:
            votes = []
            for r in range(rs.round + 1):
                pv = rs.votes.prevotes(r)
                pc = rs.votes.precommits(r)
                votes.append(
                    {
                        "round": r,
                        "prevotes_bit_array": str(pv.bit_array()) if pv else "",
                        "precommits_bit_array": str(pc.bit_array()) if pc else "",
                    }
                )
        proposal_hash = (
            rs.proposal_block.hash() if rs.proposal_block is not None else None
        )
        locked_hash = rs.locked_block.hash() if rs.locked_block is not None else None
        valid_hash = rs.valid_block.hash() if rs.valid_block is not None else None
        return {
            "round_state": {
                "height/round/step": f"{rs.height}/{rs.round}/{int(rs.step)}",
                "start_time": rs.start_time,
                "proposal_block_hash": proposal_hash.hex().upper() if proposal_hash else "",
                "locked_block_hash": locked_hash.hex().upper() if locked_hash else "",
                "valid_block_hash": valid_hash.hex().upper() if valid_hash else "",
                "height_vote_set": votes,
            }
        }

    def consensus_params(self, height: Optional[int] = None) -> dict:
        """Consensus parameters at a height from the state store
        (ref ConsensusParams rpc/core/consensus.go:299)."""
        from tendermint_tpu.state import store as sm_store

        h = int(height) if height else self.node.block_store.height() + 1
        try:
            params = sm_store.load_consensus_params(self.node.state_db, h)
        except Exception as e:
            raise RPCError(-32603, f"no consensus params for height {h}: {e}")
        return {
            "block_height": h,
            "consensus_params": {
                "block_size": {
                    "max_bytes": params.block_size.max_bytes,
                    "max_gas": params.block_size.max_gas,
                },
                "evidence": {"max_age": params.evidence.max_age},
            },
        }

    def _lane_feed(self):
        """Shared planner LaneFeed serving RPC commit-verification bursts:
        concurrent /commit?verify=1 and /validators?verify=1 queries park
        their signature rows here and fold into ONE lane-packed planner
        dispatch (verify_windows semantics, breaker + host-fallback guard
        unchanged) instead of each paying a serial per-signature loop."""
        with self._feed_mtx:
            if self._feed is None:
                from tendermint_tpu.parallel.planner import LaneFeed

                self._feed = LaneFeed(profile_kind="rpc_lane_feed")
            return self._feed

    def _verify_stored_commit(self, h: int) -> dict:
        """Verify the stored commit at height h against its validator set
        through the shared LaneFeed; returns JSON-able verdict facts."""
        from tendermint_tpu.parallel.planner import rows_from_commit
        from tendermint_tpu.state import store as sm_store
        from tendermint_tpu.types.validator_set import CommitError

        bs = self.node.block_store
        commit = bs.load_block_commit(h) or bs.load_seen_commit(h)
        if commit is None:
            raise RPCError(-32603, f"no commit for height {h}")
        try:
            vals = sm_store.load_validators(self.node.state_db, h)
        except Exception as e:
            raise RPCError(-32603, f"no validators for {h}: {e}")
        try:
            pubkeys, msgs, sigs, powers = vals.collect_commit_sigs(
                self.node.genesis_doc.chain_id, commit.block_id, h, commit
            )
        except CommitError as e:
            return {"verified": False, "reason": str(e)}
        vrow, prow = rows_from_commit(
            commit.precommits, pubkeys, msgs, sigs, powers
        )
        ticket = self._lane_feed().submit(
            vrow, prow, vals.total_voting_power()
        )
        try:
            v = ticket.result(60.0)
        except TimeoutError:
            raise RPCError(-32603, f"commit verification timed out for {h}")
        return {
            "verified": bool(v.committed),
            "sigs_ok": bool(v.sigs_ok),
            "tally": int(v.tally),
            "total_power": int(vals.total_voting_power()),
            # realized aggregation of the dispatch this row rode in
            "batch_rows": int(v.batch_rows),
            "batch_lanes": int(v.batch_lanes),
        }

    def commit(self, height: Optional[int] = None, verify=None) -> dict:
        bs = self.node.block_store
        h = int(height) if height else bs.height()
        meta = bs.load_block_meta(h)
        if meta is None:
            raise RPCError(-32603, f"no commit for height {h}")
        commit = bs.load_block_commit(h) or bs.load_seen_commit(h)
        out = {
            "signed_header": {
                "header": _header_json(meta.header),
                "commit": {
                    "block_id": {"hash": commit.block_id.hash.hex().upper()},
                    "precommits_count": sum(1 for pc in commit.precommits if pc),
                },
            },
            "canonical": bs.load_block_commit(h) is not None,
        }
        if verify:
            out["verification"] = self._verify_stored_commit(h)
        return out

    def lite_full_commit(self, height: Optional[int] = None) -> dict:
        """Codec-exact light-client material: header+commit+valsets as b64
        marshal bytes (what lite/proxy's RPCProvider consumes; JSON field
        re-serialization could never be hash-exact)."""
        from tendermint_tpu.encoding.codec import Writer
        from tendermint_tpu.state import store as sm_store

        bs = self.node.block_store
        h = int(height) if height else bs.height()
        meta = bs.load_block_meta(h)
        commit = bs.load_block_commit(h) or bs.load_seen_commit(h)
        if meta is None or commit is None:
            raise RPCError(-32603, f"no commit for height {h}")
        try:
            vals = sm_store.load_validators(self.node.state_db, h)
            next_vals = sm_store.load_validators(self.node.state_db, h + 1)
        except Exception as e:
            raise RPCError(-32603, f"no validators for {h}: {e}")
        w = Writer()
        meta.header.encode(w)
        return {
            "height": h,
            "header": _b64(w.build()),
            "commit": _b64(commit.marshal()),
            "validators": _b64(vals.marshal()),
            "next_validators": _b64(next_vals.marshal()),
        }

    def validators(self, height: Optional[int] = None, verify=None) -> dict:
        from tendermint_tpu.state import store as sm_store

        h = int(height) if height else self.node.block_store.height() + 1
        vals = sm_store.load_validators(self.node.state_db, h)
        out = {
            "block_height": h,
            "validators": [
                {
                    "address": v.address.hex().upper(),
                    "pub_key": v.pub_key.to_json_obj(),
                    "voting_power": v.voting_power,
                    "accum": v.accum,
                }
                for v in vals.validators
            ],
        }
        if verify:
            # prove the set actually signed: verify the stored commit AT
            # this height (signed by exactly this valset) through the
            # shared LaneFeed
            out["verification"] = self._verify_stored_commit(h)
        return out

    def dump_consensus_state(self) -> dict:
        rs = self.node.consensus_state.get_round_state()
        out = {
            "round_state": {
                "height": rs.height,
                "round": rs.round,
                "step": rs.step.name,
                "locked_round": rs.locked_round,
                "valid_round": rs.valid_round,
                "proposal": str(rs.proposal) if rs.proposal else None,
            }
        }
        wd = getattr(self.node, "watchdog", None)
        if wd is not None:
            out["stall"] = wd.report() or wd.status()
        return out

    def statesync(self) -> dict:
        """Snapshot restore / serving progress (chunks applied, backfill
        window, hand-off height) from the statesync reactor."""
        reactor = getattr(self.node, "statesync_reactor", None)
        if reactor is None:
            return {"enabled": False}
        return reactor.progress()

    def frontend_status(self) -> dict:
        """Light-client frontend serving stats (cache hit state, aggregator
        dispatch/occupancy counters) when [frontend] enable is on."""
        fe = getattr(self.node, "frontend", None)
        if fe is None:
            return {"enabled": False}
        out = {"enabled": True}
        out.update(fe.stats())
        return out

    def net_info(self) -> dict:
        sw = getattr(self.node, "switch", None)
        peers = []
        if sw is not None:
            for p in sw.peers.list():
                ni = p.node_info
                peers.append(
                    {
                        "node_info": {
                            "id": ni.id,
                            "listen_addr": ni.listen_addr,
                            "network": ni.network,
                            "moniker": ni.moniker,
                        },
                        "is_outbound": p.outbound,
                        "remote_ip": p.socket_addr.host if p.socket_addr else "",
                    }
                )
        return {"listening": sw is not None, "peers": peers, "n_peers": len(peers)}

    def unconfirmed_txs(self, limit: int = 30) -> dict:
        txs = self.node.mempool.reap_max_txs(int(limit))
        return {
            "n_txs": self.node.mempool.size(),
            "txs": [_b64(t) for t in txs],
        }

    def num_unconfirmed_txs(self) -> dict:
        return {"n_txs": self.node.mempool.size()}

    # tx --------------------------------------------------------------------
    def broadcast_tx_async(self, tx: str) -> dict:
        raw = base64.b64decode(tx)
        with self._broadcast_slot("async"):
            self._check_tx_guarded(raw)
        import hashlib

        return {"code": 0, "data": "", "log": "", "hash": hashlib.sha256(raw).hexdigest().upper()}

    def broadcast_tx_sync(self, tx: str) -> dict:
        raw = base64.b64decode(tx)
        with self._broadcast_slot("sync"):
            done: "queue.Queue" = queue.Queue()
            self._check_tx_guarded(raw, callback=done.put)
            try:
                res = done.get(timeout=10)
            except queue.Empty:
                raise RPCError(-32603, "CheckTx timed out")
        import hashlib

        return {
            "code": res.code,
            "data": _b64(res.data),
            "log": res.log,
            "hash": hashlib.sha256(raw).hexdigest().upper(),
        }

    def broadcast_tx_commit(self, tx: str) -> dict:
        """Subscribe to the tx event, CheckTx, wait for commit
        (rpc/core/mempool.go:152).  The in-flight slot is claimed BEFORE the
        event-bus subscription, so a shed request never leaks a
        subscription (and never holds one while rejected)."""
        raw = base64.b64decode(tx)
        import hashlib

        tx_hash = hashlib.sha256(raw).hexdigest().upper()
        with self._broadcast_slot("commit"):
            bus = self.node.event_bus
            sub_id = f"broadcast-{tx_hash}-{time.monotonic_ns()}"
            sub = bus.subscribe(
                sub_id, f"{query_for_event(EVENT_TX)} AND {TX_HASH_KEY} = '{tx_hash}'"
            )
            try:
                done: "queue.Queue" = queue.Queue()
                self._check_tx_guarded(raw, callback=done.put)
                try:
                    check_res = done.get(timeout=10)
                except queue.Empty:
                    raise RPCError(-32603, "CheckTx timed out")
                if check_res.code != abci.CODE_TYPE_OK:
                    return {
                        "check_tx": _tx_res_json(check_res),
                        "deliver_tx": {},
                        "hash": tx_hash,
                        "height": 0,
                    }
                try:
                    msg = sub.get(timeout=30)
                except queue.Empty:
                    raise RPCError(-32603, "timed out waiting for tx to be committed")
                ev = msg.data
                return {
                    "check_tx": _tx_res_json(check_res),
                    "deliver_tx": _tx_res_json(ev.result),
                    "hash": tx_hash,
                    "height": ev.height,
                }
            finally:
                try:
                    bus.unsubscribe_all(sub_id)
                except Exception:
                    pass

    def tx(self, hash: str, prove: bool = False) -> dict:
        raw_hash = bytes.fromhex(hash)
        r = self.node.tx_indexer.get(raw_hash)
        if r is None:
            raise RPCError(-32603, f"tx ({hash}) not found")
        return {
            "hash": hash.upper(),
            "height": r.height,
            "index": r.index,
            "tx_result": _tx_res_json(r.result),
            "tx": _b64(r.tx),
        }

    def tx_search(self, query: str, prove: bool = False, page: int = 1,
                  per_page: int = 30) -> dict:
        results = self.node.tx_indexer.search(query)
        page, per_page = int(page), int(per_page)
        start = (page - 1) * per_page
        sel = results[start : start + per_page]
        return {
            "txs": [
                {
                    "hash": r.hash().hex().upper(),
                    "height": r.height,
                    "index": r.index,
                    "tx_result": _tx_res_json(r.result),
                    "tx": _b64(r.tx),
                }
                for r in sel
            ],
            "total_count": len(results),
        }

    # abci ------------------------------------------------------------------
    def abci_query(self, path: str = "", data: str = "", height: int = 0,
                   prove: bool = False) -> dict:
        res = self.node.proxy_app.query.query_sync(
            abci.RequestQuery(
                data=bytes.fromhex(data) if data else b"",
                path=path,
                height=int(height),
                prove=bool(prove),
            )
        )
        return {
            "response": {
                "code": res.code,
                "log": res.log,
                "key": _b64(res.key),
                "value": _b64(res.value),
                "height": res.height,
            }
        }

    # debug / profiling ------------------------------------------------------
    def _require_unsafe(self) -> None:
        """unsafe_* routes are operator tools, gated on config.rpc.unsafe
        (the reference registers its unsafe routes conditionally,
        rpc/core/routes.go:43)."""
        if not self.node.config.rpc.unsafe:
            raise RPCError(-32601, "unsafe RPC routes are disabled (rpc.unsafe)")

    @staticmethod
    def _parse_addr_list(v) -> list:
        """JSON list or comma-separated string of id@host:port addresses."""
        if isinstance(v, str):
            v = [s for s in v.split(",") if s.strip()]
        return list(v or [])

    def _dial_addrs(self, items, label: str, persistent: bool) -> dict:
        """Shared body of dial_seeds/dial_peers (ref rpc/core/net.go:42,59)."""
        self._require_unsafe()
        from tendermint_tpu.p2p.netaddress import NetAddress

        sw = getattr(self.node, "switch", None)
        if sw is None:
            raise RPCError(-32603, "p2p switch not running")
        items = self._parse_addr_list(items)
        if not items:
            raise RPCError(-32602, f"no {label} provided")
        try:
            addrs = [NetAddress.parse(s) for s in items]
        except Exception as e:
            raise RPCError(-32602, f"bad {label} address: {e}")
        sw.dial_peers_async(addrs, persistent=persistent)
        return {"log": f"Dialing {label} in progress. See /net_info for details"}

    def dial_seeds(self, seeds=None) -> dict:
        return self._dial_addrs(seeds, "seeds", persistent=False)

    def dial_peers(self, peers=None, persistent: bool = False) -> dict:
        return self._dial_addrs(peers, "peers", persistent=bool(persistent))

    def unsafe_flush_mempool(self) -> dict:
        """Drop every pending tx (ref UnsafeFlushMempool
        rpc/core/mempool.go:264, routes.go:47)."""
        self._require_unsafe()
        self.node.mempool.flush()
        return {}

    def dump_trace(self, limit=None) -> dict:
        """Snapshot the span-tracer ring as Chrome trace-event JSON (load at
        chrome://tracing or ui.perfetto.dev).  Gated like the unsafe_*
        routes — the dump leaks internal timings and thread names.

        limit=N keeps only the newest N events (thread-name "M" metadata is
        always kept) so a full 8192-span ring can't blow up a WS frame.  The
        `anchor` pairs a wall-clock and a perf-counter reading taken at dump
        time: trace timestamps are perf_counter-based (process-local), and
        trace_merge.py needs the pair to place them on a wall timeline."""
        self._require_unsafe()
        import time as _time

        from tendermint_tpu.libs import trace

        out = trace.chrome_trace()
        events = out.get("traceEvents", [])
        meta = [e for e in events if e.get("ph") == "M"]
        spans = [e for e in events if e.get("ph") != "M"]
        total = len(spans)
        truncated = False
        if limit is not None:
            limit = int(limit)
            if limit < 0:
                raise RPCError(-32602, "limit must be >= 0")
            if total > limit:
                spans = spans[total - limit:]  # export is oldest-first
                truncated = True
        out["traceEvents"] = meta + spans
        out["enabled"] = trace.enabled()
        out["dropped"] = trace.dropped()
        out["total_events"] = total
        out["truncated"] = truncated
        out["anchor"] = {
            "wall_ns": _time.time_ns(),
            "perf_ns": _time.perf_counter_ns(),
        }
        return out

    def trace_reset(self, enable=None, capacity=None) -> dict:
        """Clear the span-tracer ring; optionally flip the tracer on/off
        (enable=true/false) and resize the ring (capacity=N)."""
        self._require_unsafe()
        from tendermint_tpu.libs import trace

        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise RPCError(-32602, "capacity must be >= 1")
        trace.reset(capacity)
        if enable is not None:
            if bool(enable):
                trace.enable()
            else:
                trace.disable()
        return {
            "enabled": trace.enabled(),
            "capacity": trace.get_tracer().capacity,
        }

    def dump_profile(self, limit=None) -> dict:
        """Snapshot the device-dispatch cost ledger: per-window rows of
        host pack / compile / device run seconds, bytes shipped, and lane
        occupancy (libs/profile.py).  Gated like dump_trace — the ledger
        leaks internal timings.  limit=N keeps the newest N entries (the
        aggregate ledger always covers the full ring)."""
        self._require_unsafe()
        from tendermint_tpu.libs.profile import get_profiler

        p = get_profiler()
        entries = p.entries()
        total = len(entries)
        truncated = False
        if limit is not None:
            limit = int(limit)
            if limit < 0:
                raise RPCError(-32602, "limit must be >= 0")
            if total > limit:
                entries = entries[total - limit:]  # oldest-first
                truncated = True
        return {
            "ledger": p.ledger(),
            "entries": entries,
            "total_entries": total,
            "truncated": truncated,
            "dropped": p.dropped,
            # health events (breaker transitions, audits, fallbacks) ride
            # their own ring — high-churn dispatch entries can't evict them
            "events": p.events(),
            "events_dropped": p.events_dropped,
        }

    def dump_device_health(self) -> dict:
        """Device verify-path health: circuit-breaker snapshot (state,
        counters, transition history), guard config knobs, the installed
        default verifier's identity, and the profiler's breaker/audit/
        fallback event ring (libs/breaker.py).  Gated like dump_trace —
        device health and timings are operator telemetry."""
        self._require_unsafe()
        from tendermint_tpu.crypto.batch import verifier_info
        from tendermint_tpu.libs.breaker import get_device_breaker, guard_config
        from tendermint_tpu.libs.profile import get_profiler

        p = get_profiler()
        events = [
            e for e in p.events()
            if e["kind"] in ("breaker", "audit_mismatch", "device_fallback")
        ]
        return {
            "breaker": get_device_breaker().snapshot(),
            "config": guard_config().as_dict(),
            "verifier": verifier_info(),
            "events": events,
            "events_dropped": p.events_dropped,
        }

    def device_breaker_reset(self, reprobe=None) -> dict:
        """Operator reset of the device circuit breaker — the ONLY way out
        of the quarantined state (a device that disagreed with the host
        oracle must not be re-admitted by timers).  reprobe=true also drops
        the default verifier so device selection reruns from scratch in
        this process (jax.devices() under JAX_PLATFORMS)."""
        self._require_unsafe()
        from tendermint_tpu.crypto import batch as _batch
        from tendermint_tpu.libs.breaker import get_device_breaker

        br = get_device_breaker()
        br.reset()
        if reprobe is not None and bool(reprobe):
            _batch.reprobe(force=True)
        return {
            "breaker": br.snapshot(),
            "verifier": _batch.verifier_info(),
        }

    def dump_flight(self, limit=None) -> dict:
        """Snapshot the consensus flight recorder: per-height lifecycle
        records (consensus/flight.py) plus the current watchdog stall
        report.  limit=N keeps the newest N height records.  Gated like
        dump_trace — per-peer vote attribution leaks topology."""
        self._require_unsafe()
        if limit is not None:
            limit = int(limit)
            if limit < 0:
                raise RPCError(-32602, "limit must be >= 0")
        out = self.node.consensus_state.flight.snapshot(limit)
        wd = getattr(self.node, "watchdog", None)
        out["stall"] = wd.report() if wd is not None else None
        return out

    def dump_critpath(self, limit=None) -> dict:
        """Snapshot the per-height critical-path analyzer: commit-latency
        waterfalls (libs/critpath.py) with per-phase seconds, the dominant
        phase, and rolling per-phase p50/p99.  limit=N keeps the newest N
        height waterfalls.  Gated like dump_flight — it is derived from the
        same lifecycle stamps."""
        self._require_unsafe()
        if limit is not None:
            limit = int(limit)
            if limit < 0:
                raise RPCError(-32602, "limit must be >= 0")
        cs = self.node.consensus_state
        out = cs.critpath.snapshot(limit)
        # waterfalls only accrue while the flight recorder stamps heights
        out["flight_enabled"] = cs.flight.enabled
        if not out["node_id"]:
            out["node_id"] = cs.flight.node_id
        return out

    def dump_quorum(self, limit=None) -> dict:
        """Snapshot the quorum-formation analyzer: per-height completion
        curves (time-to-1/3/2/3 with the pivotal validator named),
        gossip first-sighting/duplicate counts, and batch-flush
        attribution (libs/quorumtrace.py).  limit=N keeps the newest N
        height records.  Gated like dump_flight — per-peer vote
        attribution leaks topology."""
        self._require_unsafe()
        if limit is not None:
            limit = int(limit)
            if limit < 0:
                raise RPCError(-32602, "limit must be >= 0")
        cs = self.node.consensus_state
        out = cs.quorumtrace.snapshot(limit)
        # curves only accrue while the flight recorder stamps journeys
        out["flight_enabled"] = cs.flight.enabled
        if not out["node_id"]:
            out["node_id"] = cs.flight.node_id
        return out

    def quorum_reset(self, capacity=None) -> dict:
        """Clear the quorum-formation record ring and its rolling
        time-to-quorum percentile windows; optionally resize the ring
        (capacity=N)."""
        self._require_unsafe()
        qt = self.node.consensus_state.quorumtrace
        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise RPCError(-32602, "capacity must be >= 1")
        qt.reset(capacity)
        return {"capacity": qt.capacity}

    def critpath_reset(self, capacity=None) -> dict:
        """Clear the critical-path waterfall ring and its rolling phase
        percentile windows; optionally resize the ring (capacity=N)."""
        self._require_unsafe()
        cp = self.node.consensus_state.critpath
        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise RPCError(-32602, "capacity must be >= 1")
        cp.reset(capacity)
        return {"capacity": cp.capacity}

    def dump_telemetry(self, limit=None) -> dict:
        """Snapshot the telemetry spool's in-memory ring (newest periodic
        snapshots plus spool health; libs/telemetry.py) — the live
        counterpart of reading the on-disk spool segments offline.
        limit=N keeps the newest N snapshots.  Gated like dump_flight —
        snapshots embed eviction/ledger internals."""
        self._require_unsafe()
        if limit is not None:
            limit = int(limit)
            if limit < 0:
                raise RPCError(-32602, "limit must be >= 0")
        spool = getattr(self.node, "telemetry_spool", None)
        if spool is None:
            raise RPCError(
                -32603,
                "telemetry spool not running "
                "(instrumentation.telemetry_spool)",
            )
        return spool.snapshot(limit)

    def telemetry_reset(self, capacity=None) -> dict:
        """Clear the telemetry spool's in-memory snapshot ring and health
        counters; optionally resize the ring (capacity=N).  The on-disk
        spool segments are history and are NOT touched."""
        self._require_unsafe()
        spool = getattr(self.node, "telemetry_spool", None)
        if spool is None:
            raise RPCError(
                -32603,
                "telemetry spool not running "
                "(instrumentation.telemetry_spool)",
            )
        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise RPCError(-32602, "capacity must be >= 1")
        return spool.reset(capacity)

    def dump_mempool_qos(self) -> dict:
        """Per-peer mempool admission ledger (token levels, drops by
        reason, mute state), lane occupancy, and the RPC broadcast
        load-shed counters — the dump_consensus_state of the ingestion
        path.  Gated like dump_trace: per-peer traffic accounting leaks
        topology."""
        self._require_unsafe()
        reactor = getattr(self.node, "mempool_reactor", None)
        qos = (
            reactor.qos_snapshot()
            if reactor is not None and hasattr(reactor, "qos_snapshot")
            else {"enabled": False, "peers": {}}
        )
        mp = self.node.mempool
        cfg = getattr(self.node, "config", None)
        with self._broadcast_mtx:
            rpc = {
                "in_flight": self._broadcast_in_flight,
                "budget": getattr(cfg.rpc, "broadcast_max_in_flight", 0)
                if cfg else 0,
                "shed": dict(self.broadcast_shed),
            }
        return {
            "qos": qos,
            "mempool": {
                "size": mp.size(),
                "max_size": getattr(mp, "_max_size", None),
                "lane_sizes": mp.lane_sizes()
                if hasattr(mp, "lane_sizes") else [],
            },
            "rpc": rpc,
        }

    def flight_reset(self, enable=None, capacity=None) -> dict:
        """Clear the flight-recorder ring; optionally flip it on/off
        (enable=true/false) and resize the ring (capacity=N)."""
        self._require_unsafe()
        flight = self.node.consensus_state.flight
        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise RPCError(-32602, "capacity must be >= 1")
        flight.reset(capacity)
        if enable is not None:
            if bool(enable):
                flight.enable()
            else:
                flight.disable()
        return {"enabled": flight.enabled, "capacity": flight.capacity}

    def profile_reset(self, capacity=None) -> dict:
        """Clear the dispatch-cost ledger; optionally resize the ring
        (capacity=N)."""
        self._require_unsafe()
        from tendermint_tpu.libs.profile import get_profiler

        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise RPCError(-32602, "capacity must be >= 1")
        get_profiler().reset(capacity)
        return {}

    def unsafe_dump_threads(self) -> dict:
        """Stack dump of every live thread — the pprof-goroutine analogue
        (ref: pprof server at node/node.go:474-479)."""
        self._require_unsafe()
        import sys as _sys
        import traceback

        frames = _sys._current_frames()
        out = {}
        for t in threading.enumerate():
            frame = frames.get(t.ident)
            out[f"{t.name} (daemon={t.daemon})"] = (
                traceback.format_stack(frame) if frame is not None else []
            )
        return {"n_threads": len(out), "stacks": out}

    def unsafe_start_profiler(self, dir: str = "/tmp/tm_tpu_trace") -> dict:
        """Start a JAX profiler trace (xprof-compatible; SURVEY §5 —
        device-time attribution for the batched verify dispatches)."""
        self._require_unsafe()
        import jax

        jax.profiler.start_trace(dir)
        return {"tracing": True, "dir": dir}

    def unsafe_stop_profiler(self) -> dict:
        self._require_unsafe()
        import jax

        jax.profiler.stop_trace()
        return {"tracing": False}

    # reference route-name aliases (routes.go:49-51): the CPU profiler maps
    # to the JAX/xprof trace (device+host timelines), the heap profile to a
    # tracemalloc snapshot
    def unsafe_start_cpu_profiler(self, filename: str = "/tmp/tm_tpu_trace") -> dict:
        return self.unsafe_start_profiler(dir=filename)

    def unsafe_stop_cpu_profiler(self) -> dict:
        return self.unsafe_stop_profiler()

    def unsafe_write_heap_profile(self, filename: str = "tm_tpu_heap.txt") -> dict:
        """Top allocation sites by live bytes (pprof WriteHeapProfile's
        role; tracemalloc is the Python-native equivalent).

        `filename` is a bare name resolved under the system temp dir — an
        RPC caller must not get an arbitrary-file-overwrite primitive out of
        a profiling route (rpc.unsafe gating alone is thin: operators do
        enable it to profile)."""
        self._require_unsafe()
        import tempfile
        import tracemalloc

        base = os.path.basename(filename)
        if base != filename or base in ("", ".", ".."):
            raise ValueError(
                "heap profile filename must be a bare file name "
                "(written under the node's profile directory)"
            )
        # node-owned 0700 subdir + O_NOFOLLOW: a world-writable /tmp must
        # not let another local user plant a symlink where we write
        prof_dir = os.path.join(
            tempfile.gettempdir(), f"tm-tpu-profiles-{os.getuid()}"
        )
        os.makedirs(prof_dir, mode=0o700, exist_ok=True)
        os.chmod(prof_dir, 0o700)
        filename = os.path.join(prof_dir, base)
        fd = os.open(
            filename,
            os.O_WRONLY | os.O_CREAT | os.O_TRUNC | os.O_NOFOLLOW,
            0o600,
        )

        started_here = False
        if not tracemalloc.is_tracing():
            # no baseline: start now so a SECOND call sees real traffic
            tracemalloc.start()
            started_here = True
        snap = tracemalloc.take_snapshot()
        stats = snap.statistics("lineno")[:100]
        with os.fdopen(fd, "w") as f:
            for st in stats:
                f.write(f"{st.size}B in {st.count} blocks: {st.traceback}\n")
        return {
            "filename": filename,
            "top_entries": len(stats),
            "tracing_started_now": started_here,
        }

    def unsafe_stop_heap_profiler(self) -> dict:
        """Turn allocation tracing back off — tracemalloc taxes every
        allocation, so a validator must be able to disable it without a
        restart after grabbing profiles."""
        self._require_unsafe()
        import tracemalloc

        was = tracemalloc.is_tracing()
        tracemalloc.stop()
        return {"was_tracing": was}

    def abci_info(self) -> dict:
        res = self.node.proxy_app.query.info_sync(abci.RequestInfo())
        return {
            "response": {
                "data": res.data,
                "version": res.version,
                "last_block_height": res.last_block_height,
                "last_block_app_hash": _b64(res.last_block_app_hash),
            }
        }


def _header_json(h) -> dict:
    return {
        "chain_id": h.chain_id,
        "height": h.height,
        "time_ns": h.time_ns,
        "num_txs": h.num_txs,
        "total_txs": h.total_txs,
        "last_block_id": {"hash": h.last_block_id.hash.hex().upper()},
        "app_hash": h.app_hash.hex().upper(),
        "data_hash": h.data_hash.hex().upper(),
        "validators_hash": h.validators_hash.hex().upper(),
        "proposer_address": h.proposer_address.hex().upper(),
    }


def _tx_res_json(res) -> dict:
    if res is None:
        return {}
    return {
        "code": res.code,
        "data": _b64(res.data),
        "log": res.log,
        "gas_wanted": res.gas_wanted,
        "gas_used": res.gas_used,
        "tags": [
            {"key": _b64(kv.key), "value": _b64(kv.value)} for kv in res.tags
        ],
    }
