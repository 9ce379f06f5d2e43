"""The execution layer's hashes for the payloads the traffic carries:
Keccak-256 (the original Keccak padding, not SHA3-256's), RLP, the
Merkle-Patricia root of an ordered list of up to one item, the EIP-4844
blob transaction, and the execution block hash a consensus client checks
(yellow paper header, with EIP-4895 withdrawals, EIP-4844 blob gas and
EIP-4788 parent beacon root). Importing nothing of the program.
"""

from __future__ import annotations

_ROUND_CONSTANTS = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A, 0x8000000080008000,
    0x000000000000808B, 0x0000000080000001, 0x8000000080008081, 0x8000000000008009,
    0x000000000000008A, 0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089, 0x8000000000008003,
    0x8000000000008002, 0x8000000000000080, 0x000000000000800A, 0x800000008000000A,
    0x8000000080008081, 0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_ROTATIONS = [
    [0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56], [27, 20, 39, 8, 14],
]
_MASK = (1 << 64) - 1
_RATE = 136  # bytes: Keccak-256


def _rol(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _MASK if n else v


def _keccak_f(a: list) -> None:
    """Keccak-f[1600] on a[x][y], in place."""
    for rc in _ROUND_CONSTANTS:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                a[x][y] ^= d[x]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROTATIONS[x][y])
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
        a[0][0] ^= rc


def keccak256(data: bytes) -> bytes:
    padded = bytearray(data)
    padded.append(0x01)
    padded += bytes(-len(padded) % _RATE)
    padded[-1] |= 0x80
    a = [[0] * 5 for _ in range(5)]
    for off in range(0, len(padded), _RATE):
        block = padded[off : off + _RATE]
        for i in range(_RATE // 8):
            x, y = i % 5, i // 5
            a[x][y] ^= int.from_bytes(block[8 * i : 8 * i + 8], "little")
        _keccak_f(a)
    out = b"".join(a[i % 5][i // 5].to_bytes(8, "little") for i in range(4))
    return out


# --- RLP -----------------------------------------------------------------------


def _length_prefix(n: int, short: int) -> bytes:
    if n < 56:
        return bytes([short + n])
    ln = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([short + 55 + len(ln)]) + ln


def rlp(item) -> bytes:
    """RLP of bytes, a non-negative int (big-endian, no leading zeros) or
    a list of such items."""
    if isinstance(item, int):
        item = item.to_bytes((item.bit_length() + 7) // 8, "big") if item else b""
    if isinstance(item, (bytes, bytearray)):
        item = bytes(item)
        if len(item) == 1 and item[0] < 0x80:
            return item
        return _length_prefix(len(item), 0x80) + item
    body = b"".join(rlp(x) for x in item)
    return _length_prefix(len(body), 0xC0) + body


EMPTY_TRIE_ROOT = keccak256(rlp(b""))
EMPTY_OMMERS_HASH = keccak256(rlp([]))


def ordered_trie_root(items: list) -> bytes:
    """The Merkle-Patricia root of {rlp(i): items[i]}, for at most one
    item: the empty trie, or one leaf whose path is the nibbles of
    rlp(0) = 0x80 (hex-prefix flag 2: a leaf of even length)."""
    if not items:
        return EMPTY_TRIE_ROOT
    if len(items) > 1:
        raise ValueError("the reference builds payloads of at most one transaction")
    return keccak256(rlp([b"\x20" + rlp(0), items[0]]))


def blob_transaction(chain_id: int, nonce: int, versioned_hashes: list) -> bytes:
    """An EIP-4844 transaction (type 3) carrying the block's blobs'
    versioned hashes; the signature fields are placeholders, which no
    consensus-side check reads."""
    fields = [
        chain_id, nonce, 10**9, 10**11, 21000, b"\x42" * 20, 0, b"", [],
        10**9, list(versioned_hashes), 0, 1, 1,
    ]
    return b"\x03" + rlp(fields)


def execution_block_hash(payload: dict, parent_beacon_block_root: bytes) -> bytes:
    """keccak256(rlp(header)) of the execution block a Deneb payload
    describes."""
    header = [
        payload["parent_hash"], EMPTY_OMMERS_HASH, payload["fee_recipient"],
        payload["state_root"], ordered_trie_root(payload["transactions"]),
        payload["receipts_root"], payload["logs_bloom"], 0, payload["block_number"],
        payload["gas_limit"], payload["gas_used"], payload["timestamp"],
        payload["extra_data"], payload["prev_randao"], b"\x00" * 8,
        payload["base_fee_per_gas"],
        ordered_trie_root([rlp([w["index"], w["validator_index"], w["address"], w["amount"]])
                           for w in payload["withdrawals"]]),
        payload["blob_gas_used"], payload["excess_blob_gas"], parent_beacon_block_root,
    ]
    return keccak256(rlp(header))
