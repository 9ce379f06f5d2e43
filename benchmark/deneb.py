"""SSZ for the Deneb containers a full block carries, mainnet preset.

From the SSZ spec and consensus-specs deneb/beacon-chain.md, importing
nothing of the program under test. A type is ("uint", bytes) |
("bytes", n) | ("bytelist", limit) | ("bits", n) | ("bitlist", limit) |
("vector", element, n) | ("list", element, limit) | [(field, type), ...];
a container's value is a dict, a bitfield's a list of 0/1. Beside
serialize and hash_tree_root: Merkle branches (the sidecars' commitment
inclusion proof) and `Tree`, an incrementally updated Merkle tree for the
large lists and vectors of the reference state.
"""

from __future__ import annotations

from .spec import _ZERO, merkleize, sha256

U8, U64, U256 = ("uint", 1), ("uint", 8), ("uint", 32)
ROOT = ("bytes", 32)
SIG = ("bytes", 96)
PUBKEY = ("bytes", 48)

MAX_VALIDATORS_PER_COMMITTEE = 2048
MAX_BLOB_COMMITMENTS_PER_BLOCK = 4096
BYTES_PER_BLOB = 131072
KZG_COMMITMENT_INCLUSION_PROOF_DEPTH = 17

CHECKPOINT = [("epoch", U64), ("root", ROOT)]
ATTESTATION_DATA = [("slot", U64), ("index", U64), ("beacon_block_root", ROOT),
                    ("source", CHECKPOINT), ("target", CHECKPOINT)]
ATTESTATION = [("aggregation_bits", ("bitlist", MAX_VALIDATORS_PER_COMMITTEE)),
               ("data", ATTESTATION_DATA), ("signature", SIG)]
ETH1_DATA = [("deposit_root", ROOT), ("deposit_count", U64), ("block_hash", ROOT)]
SYNC_AGGREGATE = [("sync_committee_bits", ("bits", 512)), ("sync_committee_signature", SIG)]
WITHDRAWAL = [("index", U64), ("validator_index", U64), ("address", ("bytes", 20)),
              ("amount", U64)]
HEADER = [("slot", U64), ("proposer_index", U64), ("parent_root", ROOT),
          ("state_root", ROOT), ("body_root", ROOT)]
SIGNED_HEADER = [("message", HEADER), ("signature", SIG)]
# the operations a full block of this deployment leaves empty: only their
# limits enter a root, and an empty list serializes to nothing
_UNUSED = [("unused", ROOT), ("never", ROOT)]
EXECUTION_PAYLOAD = [
    ("parent_hash", ROOT), ("fee_recipient", ("bytes", 20)), ("state_root", ROOT),
    ("receipts_root", ROOT), ("logs_bloom", ("bytes", 256)), ("prev_randao", ROOT),
    ("block_number", U64), ("gas_limit", U64), ("gas_used", U64), ("timestamp", U64),
    ("extra_data", ("bytelist", 32)), ("base_fee_per_gas", U256), ("block_hash", ROOT),
    ("transactions", ("list", ("bytelist", 2**30), 2**20)),
    ("withdrawals", ("list", WITHDRAWAL, 16)),
    ("blob_gas_used", U64), ("excess_blob_gas", U64),
]
BODY = [
    ("randao_reveal", SIG), ("eth1_data", ETH1_DATA), ("graffiti", ROOT),
    ("proposer_slashings", ("list", _UNUSED, 16)),
    ("attester_slashings", ("list", _UNUSED, 2)),
    ("attestations", ("list", ATTESTATION, 128)),
    ("deposits", ("list", _UNUSED, 16)),
    ("voluntary_exits", ("list", _UNUSED, 16)),
    ("sync_aggregate", SYNC_AGGREGATE),
    ("execution_payload", EXECUTION_PAYLOAD),
    ("bls_to_execution_changes", ("list", _UNUSED, 16)),
    ("blob_kzg_commitments", ("list", PUBKEY, MAX_BLOB_COMMITMENTS_PER_BLOCK)),
]
BLOCK = [("slot", U64), ("proposer_index", U64), ("parent_root", ROOT),
         ("state_root", ROOT), ("body", BODY)]
SIGNED_BLOCK = [("message", BLOCK), ("signature", SIG)]
BLOB_SIDECAR = [
    ("index", U64), ("blob", ("bytes", BYTES_PER_BLOB)), ("kzg_commitment", PUBKEY),
    ("kzg_proof", PUBKEY), ("signed_block_header", SIGNED_HEADER),
    ("kzg_commitment_inclusion_proof", ("vector", ROOT, KZG_COMMITMENT_INCLUSION_PROOF_DEPTH)),
]


# --- serialization -------------------------------------------------------------


def is_fixed(typ) -> bool:
    if isinstance(typ, list):
        return all(is_fixed(t) for _, t in typ)
    if typ[0] in ("uint", "bytes", "bits"):
        return True
    if typ[0] == "vector":
        return is_fixed(typ[1])
    return False


def _bits_bytes(bits, with_length_bit: bool) -> bytes:
    n = len(bits)
    out = bytearray((n + with_length_bit + 7) // 8)
    for i, b in enumerate(bits):
        if b:
            out[i // 8] |= 1 << (i % 8)
    if with_length_bit:
        out[n // 8] |= 1 << (n % 8)
    return bytes(out)


def _serialize_parts(types, values) -> bytes:
    fixed, variable = [], []
    for t, v in zip(types, values):
        if is_fixed(t):
            fixed.append(serialize(t, v))
            variable.append(b"")
        else:
            fixed.append(None)
            variable.append(serialize(t, v))
    offset = sum(4 if f is None else len(f) for f in fixed)
    head = bytearray()
    for f, var in zip(fixed, variable):
        if f is None:
            head += offset.to_bytes(4, "little")
            offset += len(var)
        else:
            head += f
    return bytes(head) + b"".join(variable)


def serialize(typ, value) -> bytes:
    if isinstance(typ, list):
        return _serialize_parts([t for _, t in typ], [value[name] for name, _ in typ])
    kind = typ[0]
    if kind == "uint":
        return int(value).to_bytes(typ[1], "little")
    if kind in ("bytes", "bytelist"):
        return bytes(value)
    if kind == "bits":
        return _bits_bytes(value, False)
    if kind == "bitlist":
        return _bits_bytes(value, True)
    values = list(value)
    if is_fixed(typ[1]):
        return b"".join(serialize(typ[1], v) for v in values)
    return _serialize_parts([typ[1]] * len(values), values)


# --- hash tree root ------------------------------------------------------------


def _pack(data: bytes) -> list:
    data = data + bytes(-len(data) % 32)
    return [data[i : i + 32] for i in range(0, len(data), 32)]


def mix_in_length(root: bytes, n: int) -> bytes:
    return sha256(root + n.to_bytes(32, "little"))


def _chunk_limit(typ) -> int:
    element, n = typ[1], typ[2]
    if isinstance(element, list) or element[0] not in ("uint",):
        return n
    return (n * element[1] + 31) // 32


def field_roots(typ, value) -> list:
    """The roots of a container's fields, in order."""
    return [hash_tree_root(t, value[name]) for name, t in typ]


def hash_tree_root(typ, value) -> bytes:
    if isinstance(typ, list):
        return merkleize(field_roots(typ, value))
    kind = typ[0]
    if kind == "uint":
        return int(value).to_bytes(typ[1], "little").ljust(32, b"\x00")
    if kind == "bytes":
        if len(value) != typ[1]:
            raise ValueError(f"expected {typ[1]} bytes, got {len(value)}")
        return merkleize(_pack(bytes(value)))
    if kind == "bytelist":
        return mix_in_length(merkleize(_pack(bytes(value)), (typ[1] + 31) // 32), len(value))
    if kind == "bits":
        return merkleize(_pack(_bits_bytes(value, False)), (typ[1] + 255) // 256)
    if kind == "bitlist":
        return mix_in_length(
            merkleize(_pack(_bits_bytes(value, False)), (typ[1] + 255) // 256), len(value)
        )
    element = typ[1]
    values = list(value)
    if not isinstance(element, list) and element[0] == "uint":
        root = merkleize(_pack(b"".join(serialize(element, v) for v in values)), _chunk_limit(typ))
    else:
        root = merkleize([hash_tree_root(element, v) for v in values], _chunk_limit(typ))
    if kind == "vector":
        if len(values) != typ[2]:
            raise ValueError("vector of the wrong length")
        return root
    return mix_in_length(root, len(values))


def branch(leaves: list, index: int) -> list:
    """The Merkle branch of leaves[index] in the tree over `leaves`
    (padded with zero chunks to a power of two), leaf level first."""
    n = 1
    while n < len(leaves):
        n *= 2
    layer = list(leaves) + [bytes(32)] * (n - len(leaves))
    out = []
    while len(layer) > 1:
        out.append(layer[index ^ 1])
        layer = [sha256(layer[i] + layer[i + 1]) for i in range(0, len(layer), 2)]
        index //= 2
    return out


def commitment_inclusion_proof(body: dict, index: int) -> list:
    """deneb p2p: the branch from commitment `index` to the body root:
    inside the commitments list (12 levels), its length, then the body's
    fields (4 levels)."""
    commitments = list(body["blob_kzg_commitments"])
    leaves = [hash_tree_root(PUBKEY, c) for c in commitments]
    depth = (MAX_BLOB_COMMITMENTS_PER_BLOCK - 1).bit_length()
    inner = branch(leaves, index) if leaves else []
    # pad the branch from the used subtree up to the list's depth
    for d in range(len(inner), depth):
        inner.append(_ZERO[d])
    length = len(commitments).to_bytes(32, "little")
    fields = field_roots(BODY, body)
    outer = branch(fields, [n for n, _ in BODY].index("blob_kzg_commitments"))
    return inner + [length] + outer


# --- incremental trees -----------------------------------------------------------


class Tree:
    """A Merkle tree over `chunks` (a power-of-two count is not needed),
    its root taken at `limit` chunks, with in-place leaf updates: `set`
    marks a leaf, `root` rehashes the marked paths only."""

    def __init__(self, chunks: list, limit: int):
        self.limit = limit
        n = 1
        while n < max(1, len(chunks)):
            n *= 2
        layer = list(chunks) + [bytes(32)] * (n - len(chunks))
        self.levels = [layer]
        while len(layer) > 1:
            layer = [sha256(layer[i] + layer[i + 1]) for i in range(0, len(layer), 2)]
            self.levels.append(layer)
        self.dirty: set = set()

    def set(self, i: int, chunk: bytes):
        self.levels[0][i] = chunk
        self.dirty.add(i)

    def root(self) -> bytes:
        dirty = self.dirty
        for d in range(1, len(self.levels)):
            below, level = self.levels[d - 1], self.levels[d]
            dirty = {i // 2 for i in dirty}
            for i in dirty:
                level[i] = sha256(below[2 * i] + below[2 * i + 1])
        self.dirty = set()
        root = self.levels[-1][0]
        depth = len(self.levels) - 1
        need = max(0, self.limit - 1).bit_length()
        for d in range(depth, need):
            root = sha256(root + _ZERO[d])
        return root
