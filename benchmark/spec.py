"""The consensus-spec pieces the traffic and the reference check need.

Straight from the Ethereum consensus specs (phase0 beacon-chain.md and the
SSZ spec), importing nothing of the program under test: the swap-or-not
shuffle, committee slicing, domains and signing roots, and the SSZ bytes of
an `Attestation` (phase0 layout, unchanged through Deneb).
"""

from __future__ import annotations

import hashlib

import numpy as np

DOMAIN_BEACON_ATTESTER = bytes.fromhex("01000000")


def sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def shuffled_positions(n: int, seed: bytes, rounds: int) -> np.ndarray:
    """perm[i] = compute_shuffled_index(i, n, seed) for every i < n."""
    idx = np.arange(n, dtype=np.int64)
    blocks = (n + 255) // 256
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(sha256(seed + rb)[:8], "little") % n
        flip = (pivot + n - idx) % n
        position = np.maximum(idx, flip)
        source = np.frombuffer(
            b"".join(sha256(seed + rb + j.to_bytes(4, "little")) for j in range(blocks)),
            dtype=np.uint8,
        )
        byte = source[(position // 256) * 32 + (position % 256) // 8]
        bit = (byte >> (position % 8).astype(np.uint8)) & 1
        idx = np.where(bit == 1, flip, idx)
    return idx


def attester_seed(epoch: int, randao_mix: bytes) -> bytes:
    """get_seed(state, epoch, DOMAIN_BEACON_ATTESTER), given the mix at
    epoch + EPOCHS_PER_HISTORICAL_VECTOR - MIN_SEED_LOOKAHEAD - 1."""
    return sha256(DOMAIN_BEACON_ATTESTER + epoch.to_bytes(8, "little") + randao_mix)


class Committees:
    """One epoch's committees over an all-active registry of n validators."""

    def __init__(self, n: int, slots_per_epoch: int, max_committees: int,
                 target_size: int, rounds: int, seed: bytes):
        self.n = n
        self.slots_per_epoch = slots_per_epoch
        self.per_slot = max(1, min(max_committees, n // slots_per_epoch // target_size))
        self.count = self.per_slot * slots_per_epoch
        # all validators active, so active index i is validator i
        self.shuffled = shuffled_positions(n, seed, rounds)

    def committee(self, slot: int, index: int) -> np.ndarray:
        g = (slot % self.slots_per_epoch) * self.per_slot + index
        return self.shuffled[self.n * g // self.count : self.n * (g + 1) // self.count]


# --- SSZ -------------------------------------------------------------------


def _merkleize(chunks: list) -> bytes:
    n = 1
    while n < len(chunks):
        n *= 2
    layer = list(chunks) + [bytes(32)] * (n - len(chunks))
    while len(layer) > 1:
        layer = [sha256(layer[i] + layer[i + 1]) for i in range(0, len(layer), 2)]
    return layer[0]


def _u64(v: int) -> bytes:
    return v.to_bytes(8, "little")


def checkpoint_root(epoch: int, root: bytes) -> bytes:
    return sha256(_u64(epoch).ljust(32, b"\x00") + root)


def attestation_data_bytes(slot, index, block_root, source, target) -> bytes:
    """source/target are (epoch, root) pairs; 128 bytes."""
    return (_u64(slot) + _u64(index) + block_root
            + _u64(source[0]) + source[1] + _u64(target[0]) + target[1])


def attestation_data_root(data: bytes) -> bytes:
    slot, index = data[0:8], data[8:16]
    return _merkleize([
        slot.ljust(32, b"\x00"),
        index.ljust(32, b"\x00"),
        data[16:48],
        checkpoint_root(int.from_bytes(data[48:56], "little"), data[56:88]),
        checkpoint_root(int.from_bytes(data[88:96], "little"), data[96:128]),
    ])


def compute_domain(domain_type: bytes, fork_version: bytes, genesis_validators_root: bytes) -> bytes:
    fork_data_root = sha256(fork_version.ljust(32, b"\x00") + genesis_validators_root)
    return domain_type + fork_data_root[:28]


def signing_root(object_root: bytes, domain: bytes) -> bytes:
    return sha256(object_root + domain)


def attestation_bytes(committee_size: int, position: int, data: bytes, signature: bytes) -> bytes:
    """SSZ Attestation{aggregation_bits: Bitlist, data, signature} with one
    bit set: a 4-byte offset, the 128-byte data, the 96-byte signature, then
    the bitlist with its length bit."""
    bits = bytearray(committee_size // 8 + 1)
    bits[position // 8] |= 1 << (position % 8)
    bits[committee_size // 8] |= 1 << (committee_size % 8)
    return (4 + 128 + 96).to_bytes(4, "little") + data + signature + bytes(bits)


# --- the anchor block's root ---------------------------------------------------
#
# hash_tree_root of a Deneb BeaconState (consensus-specs deneb/beacon-chain.md,
# mainnet preset), and of the block header a node boots from: a type is
# ("uint", bytes) | ("bytes", n) | ("bytelist", limit) | ("bits", n) |
# ("vector", element, n) | ("list", element, limit) | [(field, type), ...].

_ZERO = [bytes(32)]
for _ in range(64):
    _ZERO.append(sha256(_ZERO[-1] + _ZERO[-1]))


def merkleize(chunks: list, limit: int | None = None) -> bytes:
    """The SSZ merkleization of 32-byte chunks, padded to `limit` chunks."""
    count = len(chunks) if limit is None else limit
    if len(chunks) > count:
        raise ValueError("more chunks than the limit")
    depth = max(0, count - 1).bit_length()
    layer = list(chunks) or [bytes(32)]
    for d in range(depth):
        if len(layer) % 2:
            layer.append(_ZERO[d])
        layer = [sha256(layer[i] + layer[i + 1]) for i in range(0, len(layer), 2)]
    return layer[0]


def _mix_in_length(root: bytes, n: int) -> bytes:
    return sha256(root + n.to_bytes(32, "little"))


def _pack(data: bytes) -> list:
    data = data + bytes(-len(data) % 32)
    return [data[i : i + 32] for i in range(0, len(data), 32)]


def _basic_bytes(typ, values) -> bytes | None:
    """The packed bytes of a sequence of basic values, or None if the
    element type is not basic."""
    if typ[0] == "uint":
        if typ[1] == 1:
            return bytes(values)
        return b"".join(int(v).to_bytes(typ[1], "little") for v in values)
    return None


def hash_tree_root(typ, value) -> bytes:
    if isinstance(typ, list):
        return merkleize([hash_tree_root(t, getattr(value, name)) for name, t in typ])
    kind = typ[0]
    if kind == "uint":
        return int(value).to_bytes(typ[1], "little").ljust(32, b"\x00")
    if kind == "bytes":
        value = bytes(value)
        if len(value) != typ[1]:
            raise ValueError(f"expected {typ[1]} bytes, got {len(value)}")
        return merkleize(_pack(value))
    if kind == "bytelist":
        value = bytes(value)
        return _mix_in_length(merkleize(_pack(value), (typ[1] + 31) // 32), len(value))
    if kind == "bits":
        bits = [bool(b) for b in value]
        if len(bits) != typ[1]:
            raise ValueError("bitvector of the wrong length")
        packed = bytearray((typ[1] + 7) // 8)
        for i, b in enumerate(bits):
            packed[i // 8] |= b << (i % 8)
        return merkleize(_pack(bytes(packed)), (typ[1] + 255) // 256)
    element, n = typ[1], typ[2]
    values = list(value)
    packed = _basic_bytes(element, values)
    if packed is not None:
        root = merkleize(_pack(packed), (n * element[1] + 31) // 32)
    else:
        root = merkleize([hash_tree_root(element, v) for v in values], n)
    if kind == "vector":
        if len(values) != n:
            raise ValueError("vector of the wrong length")
        return root
    return _mix_in_length(root, len(values))


U64, U8, U256 = ("uint", 8), ("uint", 1), ("uint", 32)
ROOT = ("bytes", 32)
FORK = [("previous_version", ("bytes", 4)), ("current_version", ("bytes", 4)), ("epoch", U64)]
HEADER = [("slot", U64), ("proposer_index", U64), ("parent_root", ROOT),
          ("state_root", ROOT), ("body_root", ROOT)]
ETH1_DATA = [("deposit_root", ROOT), ("deposit_count", U64), ("block_hash", ROOT)]
VALIDATOR = [("pubkey", ("bytes", 48)), ("withdrawal_credentials", ROOT),
             ("effective_balance", U64), ("slashed", ("uint", 1)),
             ("activation_eligibility_epoch", U64), ("activation_epoch", U64),
             ("exit_epoch", U64), ("withdrawable_epoch", U64)]
CHECKPOINT = [("epoch", U64), ("root", ROOT)]
SYNC_COMMITTEE = [("pubkeys", ("vector", ("bytes", 48), 512)), ("aggregate_pubkey", ("bytes", 48))]
PAYLOAD_HEADER = [
    ("parent_hash", ROOT), ("fee_recipient", ("bytes", 20)), ("state_root", ROOT),
    ("receipts_root", ROOT), ("logs_bloom", ("bytes", 256)), ("prev_randao", ROOT),
    ("block_number", U64), ("gas_limit", U64), ("gas_used", U64), ("timestamp", U64),
    ("extra_data", ("bytelist", 32)), ("base_fee_per_gas", U256), ("block_hash", ROOT),
    ("transactions_root", ROOT), ("withdrawals_root", ROOT), ("blob_gas_used", U64),
    ("excess_blob_gas", U64),
]
HISTORICAL_SUMMARY = [("block_summary_root", ROOT), ("state_summary_root", ROOT)]
REGISTRY_LIMIT = 2**40
BEACON_STATE_DENEB = [
    ("genesis_time", U64), ("genesis_validators_root", ROOT), ("slot", U64),
    ("fork", FORK), ("latest_block_header", HEADER),
    ("block_roots", ("vector", ROOT, 8192)), ("state_roots", ("vector", ROOT, 8192)),
    ("historical_roots", ("list", ROOT, 2**24)),
    ("eth1_data", ETH1_DATA), ("eth1_data_votes", ("list", ETH1_DATA, 64 * 32)),
    ("eth1_deposit_index", U64),
    ("validators", ("list", VALIDATOR, REGISTRY_LIMIT)),
    ("balances", ("list", U64, REGISTRY_LIMIT)),
    ("randao_mixes", ("vector", ROOT, 65536)),
    ("slashings", ("vector", U64, 8192)),
    ("previous_epoch_participation", ("list", U8, REGISTRY_LIMIT)),
    ("current_epoch_participation", ("list", U8, REGISTRY_LIMIT)),
    ("justification_bits", ("bits", 4)),
    ("previous_justified_checkpoint", CHECKPOINT),
    ("current_justified_checkpoint", CHECKPOINT),
    ("finalized_checkpoint", CHECKPOINT),
    ("inactivity_scores", ("list", U64, REGISTRY_LIMIT)),
    ("current_sync_committee", SYNC_COMMITTEE), ("next_sync_committee", SYNC_COMMITTEE),
    ("latest_execution_payload_header", PAYLOAD_HEADER),
    ("next_withdrawal_index", U64), ("next_withdrawal_validator_index", U64),
    ("historical_summaries", ("list", HISTORICAL_SUMMARY, 2**24)),
]


def anchor_block_root(state) -> bytes:
    """The root of the block a node boots from `state`: its latest block
    header with the state's root filled in, as the spec's genesis block."""
    header = state.latest_block_header
    state_root = header.state_root
    if bytes(state_root) == bytes(32):
        state_root = hash_tree_root(BEACON_STATE_DENEB, state)
    return merkleize([
        hash_tree_root(U64, header.slot), hash_tree_root(U64, header.proposer_index),
        bytes(header.parent_root), bytes(state_root), bytes(header.body_root),
    ])


def parse_attestation(raw: bytes):
    """(set bit positions, bitlist length, data bytes, signature) of an SSZ
    Attestation; raises ValueError on a malformed encoding."""
    if len(raw) < 229 or int.from_bytes(raw[:4], "little") != 228:
        raise ValueError("not an Attestation encoding")
    data, signature, bits = raw[4:132], raw[132:228], raw[228:]
    if not bits or bits[-1] == 0:
        raise ValueError("bitlist without its length bit")
    length = (len(bits) - 1) * 8 + bits[-1].bit_length() - 1
    positions = [i for i in range(length) if bits[i // 8] >> (i % 8) & 1]
    return positions, length, data, signature
