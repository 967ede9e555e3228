"""CLI (ref: cmd/tendermint — cobra commands at commands/).

Commands: init, node, version, gen_validator, show_validator, gen_node_key,
show_node_id, testnet, reset_all, reset_priv_validator.
Run: python -m tendermint_tpu.cmd.tendermint <command> [--home DIR] ...
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import shutil
import signal
import sys
import time

VERSION = "tpu-0.1.0 (capabilities of reference v0.26.2)"


def _home(args) -> str:
    return os.path.abspath(args.home)


def _config(args):
    from tendermint_tpu.config.config import default_config

    cfg = default_config()
    cfg.set_root(_home(args))
    if getattr(args, "proxy_app", None):
        cfg.base.proxy_app = args.proxy_app
    if getattr(args, "rpc_laddr", None):
        cfg.rpc.laddr = args.rpc_laddr
    if getattr(args, "p2p_laddr", None):
        # literal "none" disables p2p (single-node mode)
        cfg.p2p.laddr = "" if args.p2p_laddr == "none" else args.p2p_laddr
    if getattr(args, "persistent_peers", None):
        cfg.p2p.persistent_peers = args.persistent_peers
    if getattr(args, "timeout_commit", None) is not None:
        cfg.consensus.timeout_commit = args.timeout_commit
    if getattr(args, "allow_duplicate_ip", None) is not None:
        cfg.p2p.allow_duplicate_ip = args.allow_duplicate_ip == "true"
    if getattr(args, "fast_sync", None) is not None:
        cfg.base.fast_sync = args.fast_sync == "true"
    return cfg


def cmd_init(args) -> int:
    """Initialize home dir: priv validator, node key, genesis (commands/init.go)."""
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    home = _home(args)
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    cfg = _config(args)

    pv_path = cfg.base.priv_validator_path()
    if os.path.exists(pv_path):
        pv = FilePV.load(pv_path)
        print(f"Found private validator: {pv_path}")
    else:
        pv = FilePV.generate(pv_path)
        print(f"Generated private validator: {pv_path}")

    genesis_path = cfg.base.genesis_path()
    if os.path.exists(genesis_path):
        print(f"Found genesis file: {genesis_path}")
    else:
        doc = GenesisDoc(
            chain_id=args.chain_id or f"test-chain-{int(time.time())}",
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pv.get_pub_key(), 10, "")],
        )
        doc.validate_and_complete()
        doc.save_as(genesis_path)
        print(f"Generated genesis file: {genesis_path}")
    return 0


def cmd_node(args) -> int:
    """Run the node (commands/run_node.go)."""
    from tendermint_tpu.libs.log import parse_log_level, setup
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.privval.file_pv import FilePV

    cfg = _config(args)
    default, mods = parse_log_level(args.log_level)
    setup(default, mods)
    pv = FilePV.load_or_generate(cfg.base.priv_validator_path())
    node = Node(cfg, priv_validator=pv)
    node.start()
    print(f"Node started. RPC: {cfg.rpc.laddr}", flush=True)
    print(f"Batch verifier: {node.verifier_description}", flush=True)

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        node.stop()
    return 0


def cmd_version(args) -> int:
    print(VERSION)
    return 0


def cmd_probe_upnp(args) -> int:
    """Probe for a UPnP gateway (commands/probe_upnp.go)."""
    from tendermint_tpu.p2p.upnp import probe

    caps = probe()
    print(json.dumps(caps.__dict__, indent=2))
    return 0


def cmd_replay(args, console: bool = False) -> int:
    """Replay the WAL through a fresh consensus state (commands/replay.go)."""
    from tendermint_tpu.consensus.replay_file import run_replay_file

    cfg = _config(args)
    return 0 if run_replay_file(cfg, console=console) >= 0 else 1


def cmd_replay_console(args) -> int:
    return cmd_replay(args, console=True)


def cmd_lite(args) -> int:
    """Light-client verifying proxy: certify headers from an untrusted node
    via the DynamicVerifier and serve verified /status /commit locally
    (commands/lite.go + lite/proxy)."""
    from tendermint_tpu.lite.proxy import run_lite_proxy

    if (args.trusted_height is None) != (not args.trusted_hash):
        print(
            "error: --trusted-height and --trusted-hash must be given together",
            file=sys.stderr,
        )
        return 1
    trusted_hash = None
    if args.trusted_hash:
        try:
            trusted_hash = bytes.fromhex(args.trusted_hash.removeprefix("0x"))
        except ValueError:
            print("error: --trusted-hash is not valid hex", file=sys.stderr)
            return 1
        if len(trusted_hash) != 32:
            print("error: --trusted-hash must be 32 bytes of hex", file=sys.stderr)
            return 1
    return run_lite_proxy(
        chain_id=args.chain_id,
        node_addr=args.node,
        laddr=args.laddr,
        home=_home(args),
        trusted_height=args.trusted_height,
        trusted_hash=trusted_hash,
    )


def cmd_gen_validator(args) -> int:
    from tendermint_tpu.crypto.keys import PrivKeyEd25519

    pk = PrivKeyEd25519.generate()
    print(
        json.dumps(
            {
                "address": pk.pub_key().address().hex().upper(),
                "pub_key": pk.pub_key().to_json_obj(),
                "priv_key": {
                    "type": "ed25519",
                    "value": base64.b64encode(pk.bytes()).decode(),
                },
            },
            indent=2,
        )
    )
    return 0


def cmd_show_validator(args) -> int:
    from tendermint_tpu.privval.file_pv import FilePV

    cfg = _config(args)
    pv = FilePV.load(cfg.base.priv_validator_path())
    print(json.dumps(pv.get_pub_key().to_json_obj()))
    return 0


def cmd_gen_node_key(args) -> int:
    from tendermint_tpu.p2p.key import NodeKey

    cfg = _config(args)
    os.makedirs(os.path.dirname(cfg.base.node_key_path()), exist_ok=True)
    nk = NodeKey.load_or_generate(cfg.base.node_key_path())
    print(nk.id())
    return 0


def cmd_show_node_id(args) -> int:
    from tendermint_tpu.p2p.key import NodeKey

    cfg = _config(args)
    nk = NodeKey.load(cfg.base.node_key_path())
    print(nk.id())
    return 0


def cmd_reset_all(args) -> int:
    """Danger: wipe data + reset priv validator (commands/reset_priv_validator.go)."""
    from tendermint_tpu.privval.file_pv import FilePV

    cfg = _config(args)
    data = cfg.base.db_path()
    if os.path.isdir(data):
        shutil.rmtree(data)
        os.makedirs(data)
        print(f"Removed all data in {data}")
    pv_path = cfg.base.priv_validator_path()
    if os.path.exists(pv_path):
        FilePV.load(pv_path).reset()
        print(f"Reset private validator to genesis state: {pv_path}")
    return 0


def cmd_reset_priv_validator(args) -> int:
    from tendermint_tpu.privval.file_pv import FilePV

    cfg = _config(args)
    FilePV.load(cfg.base.priv_validator_path()).reset()
    print(f"Reset private validator: {cfg.base.priv_validator_path()}")
    return 0


def cmd_testnet(args) -> int:
    """Generate an N-validator testnet config tree incl. node keys and the
    persistent-peers string for a localnet (commands/testnet.go +
    docker-compose.yml's localnet wiring)."""
    from tendermint_tpu.crypto.keys import PrivKeyEd25519
    from tendermint_tpu.p2p.key import NodeKey
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    out = os.path.abspath(args.output_dir)
    n = args.v
    base_port = getattr(args, "starting_port", 26656)
    pvs, node_keys = [], []
    for i in range(n):
        node_dir = os.path.join(out, f"node{i}")
        os.makedirs(os.path.join(node_dir, "config"), exist_ok=True)
        os.makedirs(os.path.join(node_dir, "data"), exist_ok=True)
        pvs.append(
            FilePV.generate(os.path.join(node_dir, "config", "priv_validator.json"))
        )
        nk = NodeKey(PrivKeyEd25519.generate())
        nk.save_as(os.path.join(node_dir, "config", "node_key.json"))
        node_keys.append(nk)
    doc = GenesisDoc(
        chain_id=args.chain_id or f"chain-{int(time.time())}",
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator(pv.get_pub_key(), 1, f"node{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    doc.validate_and_complete()
    start_ip = getattr(args, "starting_ip_address", "") or ""
    host_prefix = getattr(args, "hostname_prefix", "") or ""
    if host_prefix:
        # kubernetes StatefulSet style: pod i is reachable at
        # <prefix>-<i>.<prefix> via the headless service
        # (testnet.go --hostname-prefix semantics; networks/kubernetes/)
        peers = ",".join(
            f"{nk.id()}@{host_prefix}-{i}.{host_prefix}:26656"
            for i, nk in enumerate(node_keys)
        )
    elif start_ip:
        # docker-network style: node i at consecutive IPs, one canonical
        # p2p port (testnet.go --starting-ip-address semantics)
        import ipaddress

        base_ip = ipaddress.ip_address(start_ip)
        peers = ",".join(
            f"{nk.id()}@{base_ip + i}:26656" for i, nk in enumerate(node_keys)
        )
    else:
        peers = ",".join(
            f"{nk.id()}@127.0.0.1:{base_port + 2 * i}"
            for i, nk in enumerate(node_keys)
        )
    for i in range(n):
        doc.save_as(os.path.join(out, f"node{i}", "config", "genesis.json"))
        with open(os.path.join(out, f"node{i}", "config", "peers.txt"), "w") as f:
            f.write(peers + "\n")
    print(f"Successfully initialized {n} node directories in {out}")
    print(f"persistent_peers: {peers}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tendermint", description=__doc__)
    p.add_argument("--home", default=os.path.expanduser("~/.tendermint_tpu"))
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="initialize a home directory")
    sp.add_argument("--chain-id", default="")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("node", help="run the node")
    sp.add_argument("--proxy_app", default="kvstore")
    sp.add_argument("--rpc.laddr", dest="rpc_laddr", default="tcp://127.0.0.1:26657")
    sp.add_argument("--p2p.laddr", dest="p2p_laddr", default="")
    sp.add_argument("--p2p.persistent_peers", dest="persistent_peers", default="")
    sp.add_argument("--consensus.timeout_commit", dest="timeout_commit",
                    type=float, default=None)
    sp.add_argument("--fast_sync", choices=["true", "false"], default=None)
    sp.add_argument("--p2p.allow_duplicate_ip", dest="allow_duplicate_ip",
                    choices=["true", "false"], default=None)
    sp.add_argument("--log_level", default="info")
    sp.set_defaults(fn=cmd_node)

    for name, fn in [
        ("version", cmd_version),
        ("gen_validator", cmd_gen_validator),
        ("show_validator", cmd_show_validator),
        ("gen_node_key", cmd_gen_node_key),
        ("show_node_id", cmd_show_node_id),
        ("probe_upnp", cmd_probe_upnp),
        ("unsafe_reset_all", cmd_reset_all),
        ("unsafe_reset_priv_validator", cmd_reset_priv_validator),
    ]:
        sp = sub.add_parser(name)
        sp.set_defaults(fn=fn)

    sp = sub.add_parser("replay", help="replay the consensus WAL")
    sp.add_argument("--proxy_app", default="kvstore")
    sp.set_defaults(fn=cmd_replay)

    sp = sub.add_parser("replay_console", help="interactive WAL replay")
    sp.add_argument("--proxy_app", default="kvstore")
    sp.set_defaults(fn=cmd_replay_console)

    sp = sub.add_parser("lite", help="light-client verifying proxy")
    sp.add_argument("--chain-id", required=True)
    sp.add_argument("--node", default="tcp://127.0.0.1:26657")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument(
        "--trusted-height", type=int, default=None,
        help="root-of-trust height verified out of band (skips TOFU seeding)",
    )
    sp.add_argument(
        "--trusted-hash", default="",
        help="hex header hash at --trusted-height; mismatch aborts",
    )
    sp.set_defaults(fn=cmd_lite)

    sp = sub.add_parser("testnet", help="generate a testnet config tree")
    sp.add_argument("--v", type=int, default=4)
    sp.add_argument("--output-dir", default="./mytestnet")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--starting-port", dest="starting_port", type=int, default=26656)
    sp.add_argument(
        "--hostname-prefix", dest="hostname_prefix", default="",
        help="peer addresses become <prefix>-<i>.<prefix>:26656 "
             "(kubernetes StatefulSet DNS; see networks/kubernetes/)",
    )
    sp.add_argument(
        "--starting-ip-address", dest="starting_ip_address", default="",
        help="peer nodes at consecutive IPs on port 26656 (docker networks)",
    )
    sp.set_defaults(fn=cmd_testnet)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
