"""PubKey/PrivKey interfaces and concrete key types.

Mirrors reference crypto/crypto.go:22-34 (PubKey: Address/Bytes/VerifyBytes/Equals,
PrivKey: Bytes/Sign/PubKey/Equals) with a JSON registry in place of amino routes.
"""

from __future__ import annotations

import base64
import hmac as _hmac
import json
from abc import ABC, abstractmethod
from dataclasses import dataclass
from typing import Callable, Dict, Type

from tendermint_tpu.crypto import ed25519 as _ed
from tendermint_tpu.crypto import secp256k1 as _secp
from tendermint_tpu.crypto.hashing import ripemd160, sha256, tmhash_truncated

ADDRESS_SIZE = 20


class PubKey(ABC):
    type_name: str = ""

    @abstractmethod
    def address(self) -> bytes: ...

    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def verify_bytes(self, msg: bytes, sig: bytes) -> bool: ...

    def equals(self, other: "PubKey") -> bool:
        return type(self) is type(other) and _hmac.compare_digest(
            self.bytes(), other.bytes()
        )

    def __eq__(self, other):  # convenience for tests/dict keys
        return isinstance(other, PubKey) and self.equals(other)

    def __hash__(self):
        return hash((self.type_name, self.bytes()))

    # -- JSON round-trip (replaces amino interface encoding) ----------------
    def to_json_obj(self) -> dict:
        return {
            "type": self.type_name,
            "value": base64.b64encode(self.bytes()).decode(),
        }


class PrivKey(ABC):
    type_name: str = ""

    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def sign(self, msg: bytes) -> bytes: ...

    @abstractmethod
    def pub_key(self) -> PubKey: ...

    def equals(self, other: "PrivKey") -> bool:
        return type(self) is type(other) and _hmac.compare_digest(
            self.bytes(), other.bytes()
        )

    def to_json_obj(self) -> dict:
        return {
            "type": self.type_name,
            "value": base64.b64encode(self.bytes()).decode(),
        }


# ---------------------------------------------------------------------------
# Ed25519 (reference crypto/ed25519/ed25519.go)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PubKeyEd25519(PubKey):
    data: bytes  # 32 bytes
    type_name = "tendermint/PubKeyEd25519"

    def __post_init__(self):
        if len(self.data) != 32:
            raise ValueError("ed25519 pubkey must be 32 bytes")

    def address(self) -> bytes:
        # reference crypto/ed25519/ed25519.go:138 — SHA256(pubkey)[:20];
        # memoized: address() sits under every valset sort/lookup
        addr = self.__dict__.get("_addr")
        if addr is None:
            addr = tmhash_truncated(self.data)
            object.__setattr__(self, "_addr", addr)
        return addr

    def bytes(self) -> bytes:
        return self.data

    def verify_bytes(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != 64:
            return False
        return _ed.verify(self.data, msg, sig)

    def __hash__(self):
        return hash(self.data)


@dataclass(frozen=True)
class PrivKeyEd25519(PrivKey):
    data: bytes  # 64 bytes: seed || pubkey
    type_name = "tendermint/PrivKeyEd25519"

    def __post_init__(self):
        if len(self.data) != 64:
            raise ValueError("ed25519 privkey must be 64 bytes")

    def bytes(self) -> bytes:
        return self.data

    def sign(self, msg: bytes) -> bytes:
        return _ed.sign(self.data, msg)

    def pub_key(self) -> PubKeyEd25519:
        return PubKeyEd25519(self.data[32:])

    @staticmethod
    def generate(seed: bytes | None = None) -> "PrivKeyEd25519":
        return PrivKeyEd25519(_ed.gen_privkey(seed))

    @staticmethod
    def from_secret(secret: bytes) -> "PrivKeyEd25519":
        """reference GenPrivKeyFromSecret: seed = SHA256(secret)."""
        return PrivKeyEd25519(_ed.gen_privkey(sha256(secret)))


# ---------------------------------------------------------------------------
# secp256k1 (reference crypto/secp256k1/secp256k1.go)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PubKeySecp256k1(PubKey):
    data: bytes  # 33-byte compressed point
    type_name = "tendermint/PubKeySecp256k1"

    def __post_init__(self):
        if len(self.data) != 33:
            raise ValueError("secp256k1 pubkey must be 33 bytes (compressed)")

    def address(self) -> bytes:
        # bitcoin-style: RIPEMD160(SHA256(pubkey)) — secp256k1.go:121
        return ripemd160(sha256(self.data))

    def bytes(self) -> bytes:
        return self.data

    def verify_bytes(self, msg: bytes, sig: bytes) -> bool:
        # message is SHA256-premixed; signature is DER, low-s enforced
        # (secp256k1.go:140-153)
        return _secp.verify(self.data, sha256(msg), sig)

    def __hash__(self):
        return hash(self.data)


@dataclass(frozen=True)
class PrivKeySecp256k1(PrivKey):
    data: bytes  # 32 bytes
    type_name = "tendermint/PrivKeySecp256k1"

    def __post_init__(self):
        if len(self.data) != 32:
            raise ValueError("secp256k1 privkey must be 32 bytes")

    def bytes(self) -> bytes:
        return self.data

    def sign(self, msg: bytes) -> bytes:
        # reference signs SHA256(msg) and emits DER (secp256k1.go:58-67)
        return _secp.sign(self.data, sha256(msg))

    def pub_key(self) -> PubKeySecp256k1:
        return PubKeySecp256k1(_secp.pubkey_compressed(self.data))

    @staticmethod
    def generate(seed: bytes | None = None) -> "PrivKeySecp256k1":
        return PrivKeySecp256k1(_secp.gen_privkey(seed))

    @staticmethod
    def from_secret(secret: bytes) -> "PrivKeySecp256k1":
        return PrivKeySecp256k1(_secp.privkey_from_secret(secret))


# ---------------------------------------------------------------------------
# Registry (amino-route replacement)
# ---------------------------------------------------------------------------

def _multisig_from_bytes(data: bytes) -> PubKey:
    # crypto/multisig imports this module, so it is looked up at the decode
    from tendermint_tpu.crypto.multisig import PubKeyMultisigThreshold

    return PubKeyMultisigThreshold.from_bytes(data)


# type name -> the key from its bytes()
_PUBKEY_TYPES: Dict[str, Callable[[bytes], PubKey]] = {
    PubKeyEd25519.type_name: PubKeyEd25519,
    PubKeySecp256k1.type_name: PubKeySecp256k1,
    "tendermint/PubKeyMultisigThreshold": _multisig_from_bytes,
}
_PRIVKEY_TYPES: Dict[str, Type[PrivKey]] = {
    PrivKeyEd25519.type_name: PrivKeyEd25519,
    PrivKeySecp256k1.type_name: PrivKeySecp256k1,
}


def pubkey_from_json_obj(obj: dict) -> PubKey:
    cls = _PUBKEY_TYPES[obj["type"]]
    return cls(base64.b64decode(obj["value"]))


def privkey_from_json_obj(obj: dict) -> PrivKey:
    cls = _PRIVKEY_TYPES[obj["type"]]
    return cls(base64.b64decode(obj["value"]))


def pubkey_to_json(pk: PubKey) -> str:
    return json.dumps(pk.to_json_obj())
