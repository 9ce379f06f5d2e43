"""Batched SHA-256 on device (JAX), specialised for SSZ Merkleization.

Merkleization is two-to-one hashing of 32-byte nodes: each parent =
SHA-256(left || right) over exactly 64 bytes of input. A 64-byte message is two
compression-function applications (the second block is the constant padding
block), so one tree level over N nodes = 2N batched compressions with zero
data-dependent control flow — ideal for the TPU VPU.

The compression rounds run in a `lax.fori_loop` (compact HLO; the batch
dimension provides all the parallelism), with a 16-word circular message
schedule held in registers. Big tree levels hash on device; the small top of
the tree finishes on host where dispatch overhead would dominate.

Reference equivalents: `ethereum_hashing` (SHA-256 w/ CPU SIMD dispatch) and
the level-by-level re-hash loop of consensus/cached_tree_hash/src/cache.rs:98-147.

All arrays are uint32 big-endian words: a 32-byte node is a row of 8 words.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# fmt: off
_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3, 0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13, 0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208, 0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_IV = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)
# fmt: on

# Padding block for a message of exactly 64 bytes: 0x80, zeros, bit-length 512.
_PAD64 = np.zeros(16, dtype=np.uint32)
_PAD64[0] = 0x80000000
_PAD64[15] = 512

# Tree levels with at most this many parent nodes finish on host.
_HOST_TOP = 1 << 8


def _rotr(x, n):
    return (x >> n) | (x << (32 - n))


def _compress(state, block):
    """One SHA-256 compression. state: [N, 8] u32, block: [N, 16] u32.

    Message schedule kept as a [N, 16] circular buffer indexed mod 16; both the
    schedule recurrence and the round update run inside one fori_loop so the
    compiled program stays small (XLA vectorizes over N).
    """
    k = jnp.asarray(_K)

    def round_fn(t, carry):
        a, b, c, d, e, f, g, h, w = carry
        i = t & 15
        wt = lax.cond(
            t < 16,
            lambda: lax.dynamic_index_in_dim(w, i, axis=1, keepdims=False),
            lambda: (
                lax.dynamic_index_in_dim(w, i, axis=1, keepdims=False)
                + _ssig0(lax.dynamic_index_in_dim(w, (t + 1) & 15, axis=1, keepdims=False))
                + lax.dynamic_index_in_dim(w, (t + 9) & 15, axis=1, keepdims=False)
                + _ssig1(lax.dynamic_index_in_dim(w, (t + 14) & 15, axis=1, keepdims=False))
            ),
        )
        w = lax.cond(
            t < 16,
            lambda: w,
            lambda: lax.dynamic_update_index_in_dim(w, wt, i, axis=1),
        )
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + k[t] + wt
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = S0 + maj
        return (t1 + t2, a, b, c, d + t1, e, f, g, w)

    init = tuple(state[:, i] for i in range(8)) + (block,)
    a, b, c, d, e, f, g, h, _ = lax.fori_loop(0, 64, round_fn, init)
    return jnp.stack([a, b, c, d, e, f, g, h], axis=-1) + state


def _ssig0(x):
    return _rotr(x, 7) ^ _rotr(x, 18) ^ (x >> 3)


def _ssig1(x):
    return _rotr(x, 17) ^ _rotr(x, 19) ^ (x >> 10)


@jax.jit
def sha256_pairs(blocks):
    """Hash N 64-byte messages: blocks [N, 16] u32 -> digests [N, 8] u32."""
    n = blocks.shape[0]
    iv = jnp.broadcast_to(jnp.asarray(_IV), (n, 8))
    st = _compress(iv, blocks)
    pad = jnp.broadcast_to(jnp.asarray(_PAD64), (n, 16))
    return _compress(st, pad)


def merkle_tree_levels(leaves):
    """All levels of the Merkle tree over a power-of-two number of leaf nodes.

    leaves: [N, 8] u32 (device or numpy), N a power of two. Returns list of
    arrays, index 0 = root level [1, 8], last = leaves. Big levels hash on
    device (one batched kernel call each, arrays stay on device); the small
    top of the tree finishes on host.
    """
    levels = [jnp.asarray(leaves)]
    nodes = levels[0]
    while nodes.shape[0] > max(_HOST_TOP, 1):
        nodes = sha256_pairs(nodes.reshape(-1, 16))
        levels.append(nodes)
    # Finish on host (batched host hasher; ≤ _HOST_TOP rows per level).
    from ..utils.sha256_batch import hash_rows

    # order="C": a device array may come back with strided host layout,
    # and the byte view below needs a contiguous row
    host = np.asarray(nodes)
    while host.shape[0] > 1:
        rows = host.astype(">u4", order="C").view(np.uint8).reshape(-1, 64)
        host = (
            np.ascontiguousarray(hash_rows(rows))
            .view(">u4")
            .astype(np.uint32)
            .reshape(-1, 8)
        )
        levels.append(host)
    return levels[::-1]


def merkleize_device(leaves):
    """Merkle root of a power-of-two number of leaves. Returns [8] u32."""
    n = leaves.shape[0]
    assert n & (n - 1) == 0, f"leaf count {n} not a power of two"
    return np.asarray(merkle_tree_levels(leaves)[0][0])


def device_hash_rows(pairs: np.ndarray) -> np.ndarray:
    """[n, 64] uint8 → [n, 32] uint8 two-to-one hashing on device.

    Pads the row count to a power of two so each size class compiles once
    (one fused kernel call for the whole batch). This is the `device`
    mode of utils.sha256_batch.hash_rows — opt-in: on hosts without a
    real accelerator the per-shape XLA compile dwarfs the hashing.
    """
    m = pairs.shape[0]
    if m == 0:
        return np.zeros((0, 32), dtype=np.uint8)
    mp = 1 << (m - 1).bit_length()
    words = np.zeros((mp, 16), dtype=np.uint32)
    words[:m] = (
        np.ascontiguousarray(pairs).view(">u4").astype(np.uint32).reshape(m, 16)
    )
    dig = np.asarray(sha256_pairs(words))[:m]
    return dig.astype(">u4", order="C").view(np.uint8).reshape(m, 32)


def bytes_to_words(data: bytes) -> np.ndarray:
    """32-byte-node buffer -> [N, 8] u32 big-endian words."""
    assert len(data) % 32 == 0
    return np.frombuffer(data, dtype=">u4").astype(np.uint32).reshape(-1, 8)


def words_to_bytes(words) -> bytes:
    return np.asarray(words, dtype=np.uint32).astype(">u4").tobytes()
