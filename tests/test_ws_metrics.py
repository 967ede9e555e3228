"""WebSocket event subscription + Prometheus metrics over a real node
(ref: rpc/lib/server/ws_handler_test.go, the subscribe route at
rpc/core/routes.go:11, metrics at node/node.go:698).
"""

import base64
import http.client
import json
import os
import socket
import struct
import threading
import time

import pytest

from tendermint_tpu.rpc.websocket import OP_TEXT, read_message

from tests.consensus_harness import wait_for


# -- a minimal masked-frame WS client ----------------------------------------------


class WSClient:
    def __init__(self, host, port, path="/websocket"):
        self.sock = socket.create_connection((host, port), timeout=10)
        key = base64.b64encode(os.urandom(16)).decode()
        req = (
            f"GET {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Upgrade: websocket\r\nConnection: Upgrade\r\n"
            f"Sec-WebSocket-Key: {key}\r\nSec-WebSocket-Version: 13\r\n\r\n"
        )
        self.sock.sendall(req.encode())
        self.rfile = self.sock.makefile("rb")
        status = self.rfile.readline()
        assert b"101" in status, status
        while self.rfile.readline() not in (b"\r\n", b""):
            pass

    def send_json(self, obj) -> None:
        payload = json.dumps(obj).encode()
        mask = os.urandom(4)
        head = bytes([0x80 | OP_TEXT])
        n = len(payload)
        if n < 126:
            head += bytes([0x80 | n])
        else:
            head += bytes([0x80 | 126]) + struct.pack(">H", n)
        masked = bytes(b ^ mask[i % 4] for i, b in enumerate(payload))
        self.sock.sendall(head + mask + masked)

    def recv_json(self, timeout=15):
        self.sock.settimeout(timeout)
        msg = read_message(self.rfile)
        assert msg is not None, "connection closed"
        opcode, payload = msg
        assert opcode == OP_TEXT, opcode
        return json.loads(payload)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


# -- node fixture ------------------------------------------------------------------


@pytest.fixture()
def live_node(tmp_path):
    from tendermint_tpu.config.config import default_config, test_config
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    home = str(tmp_path / "node")
    cfg = default_config()
    cfg.set_root(home)
    cfg.base.proxy_app = "kvstore"
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = ""
    cfg.consensus = test_config().consensus
    # real WAL: the trace-export test asserts wal.fsync spans show up e2e
    cfg.consensus.wal_path = "data/cs.wal/wal"
    cfg.instrumentation.prometheus = True
    cfg.rpc.unsafe = True
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    pv = FilePV.generate(os.path.join(home, "config", "pv.json"))
    doc = GenesisDoc(
        chain_id="ws-chain",
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.get_pub_key(), 10)],
    )
    doc.validate_and_complete()
    node = Node(cfg, priv_validator=pv, genesis_doc=doc)
    node.start()
    try:
        assert wait_for(lambda: node.block_store.height() >= 1, timeout=30)
        yield node
    finally:
        node.stop()


def _rpc_get(node, path):
    conn = http.client.HTTPConnection("127.0.0.1", node.rpc_server.bound_port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


class TestWebSocketSubscribe:
    def test_subscribe_new_block_events(self, live_node):
        ws = WSClient("127.0.0.1", live_node.rpc_server.bound_port)
        try:
            ws.send_json(
                {"jsonrpc": "2.0", "id": 7, "method": "subscribe",
                 "params": {"query": "tm.event = 'NewBlock'"}}
            )
            ack = ws.recv_json()
            assert ack["id"] == 7 and "error" not in ack
            ev = ws.recv_json()
            assert ev["id"] == "7#event"
            data = ev["result"]["data"]
            assert data["type"] == "NewBlock"
            assert data["value"]["block"]["header"]["height"] >= 1
        finally:
            ws.close()

    def test_subscribe_tx_event_on_broadcast(self, live_node):
        ws = WSClient("127.0.0.1", live_node.rpc_server.bound_port)
        try:
            ws.send_json(
                {"jsonrpc": "2.0", "id": 1, "method": "subscribe",
                 "params": {"query": "tm.event = 'Tx'"}}
            )
            assert "error" not in ws.recv_json()
            tx = b"ws-key=ws-val"
            live_node.mempool.check_tx(tx)
            ev = ws.recv_json(timeout=30)
            assert ev["result"]["data"]["type"] == "Tx"
            got_tx = base64.b64decode(ev["result"]["data"]["value"]["TxResult"]["tx"])
            assert got_tx == tx
        finally:
            ws.close()

    def test_unsubscribe_stops_events(self, live_node):
        ws = WSClient("127.0.0.1", live_node.rpc_server.bound_port)
        try:
            ws.send_json(
                {"jsonrpc": "2.0", "id": 2, "method": "subscribe",
                 "params": {"query": "tm.event = 'NewBlock'"}}
            )
            assert "error" not in ws.recv_json()
            ws.recv_json()  # at least one event flows
            ws.send_json(
                {"jsonrpc": "2.0", "id": 3, "method": "unsubscribe",
                 "params": {"query": "tm.event = 'NewBlock'"}}
            )
            # drain until the unsubscribe ack (events may be in flight)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                msg = ws.recv_json()
                if msg.get("id") == 3:
                    break
            else:
                pytest.fail("no unsubscribe ack")
            # after the ack: no further events
            with pytest.raises(Exception):
                ws.recv_json(timeout=1.0)
        finally:
            ws.close()

    def test_bad_query_returns_error(self, live_node):
        ws = WSClient("127.0.0.1", live_node.rpc_server.bound_port)
        try:
            ws.send_json(
                {"jsonrpc": "2.0", "id": 4, "method": "nope", "params": {}}
            )
            assert ws.recv_json()["error"]["code"] == -32601
        finally:
            ws.close()


class TestPrometheusMetrics:
    def test_metrics_scrape(self, live_node):
        assert wait_for(lambda: live_node.block_store.height() >= 2, timeout=30)
        # let the metrics pump observe at least one block
        assert wait_for(
            lambda: b"tendermint_consensus_height" in _rpc_get(live_node, "/metrics")[1],
            timeout=15,
        )
        status, body = _rpc_get(live_node, "/metrics")
        assert status == 200
        text = body.decode()
        for needle in (
            "# TYPE tendermint_consensus_height gauge",
            "tendermint_consensus_validators 1",
            "tendermint_mempool_size",
            "tendermint_state_block_processing_time_count",
            "tendermint_consensus_block_interval_seconds_bucket",
            # verify pipeline: height-2+ commits batch through the process
            # verifier, so the attached tendermint_verify_* family has data
            "# TYPE tendermint_verify_batch_size histogram",
            "tendermint_verify_batch_size_bucket",
            'tendermint_verify_dispatch_seconds_bucket{backend="host"',
            'tendermint_verify_calls_total{backend="host",algo="ed25519"}',
        ):
            assert needle in text, f"missing {needle}\n{text[:1500]}"
        # height gauge tracks the chain
        height_line = next(
            l for l in text.splitlines()
            if l.startswith("tendermint_consensus_height ")
        )
        assert float(height_line.split()[-1]) >= 1
        # the host verifier has recorded at least one commit's signatures
        calls_line = next(
            l for l in text.splitlines()
            if l.startswith('tendermint_verify_calls_total{backend="host"')
        )
        assert float(calls_line.split()[-1]) >= 1

    def test_metrics_route_200_when_disabled(self, live_node):
        """Scrapers must distinguish 'instrumentation off' (200 + comment)
        from 'no such route' (404)."""
        saved = live_node.metrics
        live_node.metrics = None
        try:
            status, body = _rpc_get(live_node, "/metrics")
            assert status == 200
            assert body.startswith(b"# metrics disabled")
        finally:
            live_node.metrics = saved


class TestDebugRoutes:
    def test_unsafe_dump_threads(self, live_node):
        status, body = _rpc_get(live_node, "/unsafe_dump_threads")
        assert status == 200
        import json as _json

        out = _json.loads(body)["result"]
        assert out["n_threads"] >= 3
        assert any("consensus" in name.lower() or "MainThread" in name
                   for name in out["stacks"])

    def test_unsafe_routes_gated(self, live_node):
        live_node.config.rpc.unsafe = False
        try:
            _, body = _rpc_get(live_node, "/unsafe_dump_threads")
            import json as _json

            assert "error" in _json.loads(body)
        finally:
            live_node.config.rpc.unsafe = True


class TestTraceExport:
    def test_trace_reset_and_dump(self, live_node):
        """Enable the tracer over RPC, let consensus commit a block, and pull
        a Chrome trace with consensus-step and WAL-fsync spans."""
        from tendermint_tpu.libs import trace

        _, body = _rpc_get(live_node, "/trace_reset?enable=true")
        try:
            res = json.loads(body)["result"]
            assert res["enabled"] is True
            # a whole height must pass while tracing: the one under way may
            # have done its fsyncs before the tracer came on, and its block is
            # stored before its EndHeight fsync
            h0 = live_node.block_store.height()
            assert wait_for(
                lambda: live_node.block_store.height() >= h0 + 2, timeout=30
            )
            status, body = _rpc_get(live_node, "/dump_trace")
            assert status == 200
            doc = json.loads(body)["result"]
            assert doc["displayTimeUnit"] == "ms"
            events = doc["traceEvents"]
            names = {e["name"] for e in events}
            assert "consensus.step" in names
            assert "wal.fsync" in names
            assert "thread_name" in names  # metadata events
            # every event is well-formed Chrome trace JSON
            for e in events:
                assert e["ph"] in ("X", "i", "M")
                if e["ph"] == "X":
                    assert e["dur"] >= 0 and "ts" in e
                if e["ph"] == "i":
                    assert e["s"] == "t"
            step = next(e for e in events if e["name"] == "consensus.step")
            assert step["args"]["height"] >= 1
        finally:
            trace.disable()
            trace.reset()

    def test_trace_routes_gated(self, live_node):
        from tendermint_tpu.libs import trace

        live_node.config.rpc.unsafe = False
        try:
            for route in ("/dump_trace", "/trace_reset"):
                _, body = _rpc_get(live_node, route)
                assert "error" in json.loads(body)
            assert not trace.enabled()
        finally:
            live_node.config.rpc.unsafe = True


class TestHotPathMetricsScrape:
    def test_new_families_on_live_node(self, live_node):
        """The hot-path families land on /metrics of a running node: the
        consensus loop drives step_duration, the WAL drives fsync timings,
        and a checked tx drives the mempool size histogram.  (No p2p peers
        here, so the per-peer families expose TYPE lines only.)"""
        assert wait_for(lambda: live_node.block_store.height() >= 2, timeout=30)
        live_node.mempool.check_tx(b"hot-key=hot-val")
        assert wait_for(
            lambda: b"tendermint_mempool_tx_size_bytes_count 1"
            in _rpc_get(live_node, "/metrics")[1],
            timeout=15,
        )
        text = _rpc_get(live_node, "/metrics")[1].decode()
        for needle in (
            "# TYPE tendermint_consensus_step_duration_seconds histogram",
            "# TYPE tendermint_consensus_vote_arrival_latency_seconds histogram",
            "# TYPE tendermint_consensus_wal_append_seconds histogram",
            "# TYPE tendermint_consensus_wal_fsync_seconds histogram",
            "# TYPE tendermint_p2p_peer_receive_bytes_total counter",
            "# TYPE tendermint_p2p_peer_send_bytes_total counter",
            "# TYPE tendermint_p2p_peer_pending_send_bytes gauge",
            "# TYPE tendermint_p2p_messages_received_total counter",
            "# TYPE tendermint_p2p_messages_sent_total counter",
            "# TYPE tendermint_mempool_tx_size_bytes histogram",
            "# TYPE tendermint_mempool_failed_txs counter",
            "# TYPE tendermint_mempool_recheck_times counter",
            "# TYPE tendermint_consensus_rounds gauge",
        ):
            assert needle in text, f"missing {needle}"
        # a committing node has left NEW_HEIGHT/COMMIT steps behind it
        count_line = next(
            l for l in text.splitlines()
            if l.startswith("tendermint_consensus_step_duration_seconds_count")
        )
        assert float(count_line.split()[-1]) >= 1
        # WAL fsyncs every commit
        fsync_line = next(
            l for l in text.splitlines()
            if l.startswith("tendermint_consensus_wal_fsync_seconds_count")
        )
        assert float(fsync_line.split()[-1]) >= 1
        # single-validator consensus signs prevotes+precommits each height
        vote_line = next(
            l for l in text.splitlines()
            if l.startswith(
                'tendermint_consensus_vote_arrival_latency_seconds_count'
            )
        )
        assert float(vote_line.split()[-1]) >= 1


class TestProfileExport:
    def test_dump_profile_and_reset(self, live_node):
        from tendermint_tpu.libs.profile import get_profiler

        p = get_profiler()
        p.reset()
        try:
            with p.window(42, heights=3):
                p.record("pallas", bucket=(4, 16), lanes_present=3,
                         lanes_dispatched=4, pack_seconds=0.01,
                         run_seconds=0.2, compiled=True, bytes_to_device=512)
            status, body = _rpc_get(live_node, "/dump_profile")
            assert status == 200
            out = json.loads(body)["result"]
            assert out["dropped"] == 0
            assert len(out["entries"]) == 1
            row = out["ledger"][0]
            assert row["height_base"] == 42
            assert row["heights"] == 3
            assert row["compiles"] == 1
            assert row["bytes_to_device"] == 512
            assert row["occupancy"] == 0.75
            # reset clears and resizes the ring
            _, body = _rpc_get(live_node, "/profile_reset?capacity=2")
            assert "error" not in json.loads(body)
            out = json.loads(_rpc_get(live_node, "/dump_profile")[1])["result"]
            assert out["entries"] == [] and out["ledger"] == []
            for _ in range(3):
                p.record("host")
            out = json.loads(_rpc_get(live_node, "/dump_profile")[1])["result"]
            assert len(out["entries"]) == 2 and out["dropped"] == 1
        finally:
            p.reset()

    def test_profile_reset_rejects_bad_capacity(self, live_node):
        _, body = _rpc_get(live_node, "/profile_reset?capacity=0")
        assert "error" in json.loads(body)

    def test_profile_routes_gated(self, live_node):
        live_node.config.rpc.unsafe = False
        try:
            for route in ("/dump_profile", "/profile_reset"):
                _, body = _rpc_get(live_node, route)
                assert "error" in json.loads(body)
        finally:
            live_node.config.rpc.unsafe = True


class TestFlightExport:
    def test_flight_reset_dump_and_limit(self, live_node):
        """Enable the per-node flight recorder over RPC, let a couple of
        heights commit, and pull limited + full dumps."""
        _, body = _rpc_get(live_node, "/flight_reset?enable=true")
        try:
            assert json.loads(body)["result"]["enabled"] is True
            h0 = live_node.block_store.height()
            assert wait_for(
                lambda: live_node.block_store.height() >= h0 + 2, timeout=30
            )
            status, body = _rpc_get(live_node, "/dump_flight")
            assert status == 200
            out = json.loads(body)["result"]
            assert out["enabled"] is True
            assert out["truncated"] is False
            assert out["total_records"] == len(out["records"]) >= 2
            # default-on watchdog contributes the stall key (healthy: null)
            assert "stall" in out and out["stall"] is None
            # the newest record may still be mid-height: assert on a fully
            # executed one (commit stamps before apply_block finishes)
            done = [r for r in out["records"] if r["exec"] is not None]
            assert done, "no executed height in flight records"
            rec = done[-1]
            assert rec["commit"] is not None and rec["commit"]["hash"]
            assert rec["prevote"]["count"] >= 1  # single validator: own vote
            assert rec["prevote"]["by_peer"].get("local", 0) >= 1
            assert rec["exec"]["dur_ns"] >= 0
            # limit keeps the newest record and flags the cut
            cut = json.loads(
                _rpc_get(live_node, "/dump_flight?limit=1")[1]
            )["result"]
            assert len(cut["records"]) == 1 and cut["truncated"] is True
            # >= not ==: the node may have started a new height in between
            assert cut["records"][0]["height"] >= out["records"][-1]["height"]
        finally:
            _rpc_get(live_node, "/flight_reset?enable=false")

    def test_dump_trace_limit_and_anchor(self, live_node):
        from tendermint_tpu.libs import trace

        _rpc_get(live_node, "/trace_reset?enable=true")
        try:
            h0 = live_node.block_store.height()
            assert wait_for(
                lambda: live_node.block_store.height() >= h0 + 1, timeout=30
            )
            out = json.loads(
                _rpc_get(live_node, "/dump_trace?limit=5")[1]
            )["result"]
            spans = [e for e in out["traceEvents"] if e["ph"] != "M"]
            assert len(spans) <= 5
            assert out["total_events"] > 5 and out["truncated"] is True
            # the wall/perf anchor pair trace_merge.py rebases with
            anchor = out["anchor"]
            assert anchor["wall_ns"] > 0 and anchor["perf_ns"] > 0
        finally:
            trace.disable()
            trace.reset()

    def test_flight_routes_gated(self, live_node):
        live_node.config.rpc.unsafe = False
        try:
            for route in ("/dump_flight", "/flight_reset"):
                _, body = _rpc_get(live_node, route)
                assert "error" in json.loads(body)
        finally:
            live_node.config.rpc.unsafe = True

    def test_flight_rejects_bad_args(self, live_node):
        _, body = _rpc_get(live_node, "/flight_reset?capacity=0")
        assert "error" in json.loads(body)
        _, body = _rpc_get(live_node, "/dump_flight?limit=-1")
        assert "error" in json.loads(body)

    def test_health_and_dump_consensus_state_carry_watchdog(self, live_node):
        _, body = _rpc_get(live_node, "/health")
        h = json.loads(body)["result"]
        assert h["stalled"] is False and h["stalls_total"] == 0
        _, body = _rpc_get(live_node, "/dump_consensus_state")
        out = json.loads(body)["result"]
        assert out["stall"]["stalled"] is False
