"""The blobs the traffic carries, their KZG commitments and proofs, and the
reference's check of them, on the mainnet trusted setup (the KZG ceremony
output, read as data from the copy the repository packages).

EIP-4844 / consensus-specs deneb polynomial-commitments.md, importing
nothing of the program. A blob here is the evaluation form of a seeded
line p(x) = a0 + a1 x over the bit-reversed domain of 4096 roots of unity:
every one of its 4096 field elements differs, and a verifier's work (the
challenge hash over 128 KiB, the barycentric evaluation, the pairing)
does not depend on what the blob holds. For such a blob the commitment is
[p(tau)] = a0 G1 + a1 [tau]G1 and the proof [q(tau)], q = (p(x) - p(z))
/ (x - z) = a1, is a1 G1 whatever the challenge z: both are unique, so a
sidecar's proof is valid exactly when it equals the one computed here.
[tau]G1 is sum_i w^i L_i, the Lagrange points weighted by the domain.
"""

from __future__ import annotations

import hashlib
import json
import os
import random

from . import bls
from .bls import G1, P, R

FIELD_ELEMENTS_PER_BLOB = 4096
PRIMITIVE_ROOT_OF_UNITY = 7
VERSIONED_HASH_VERSION_KZG = b"\x01"
SETUP = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "lighthouse_tpu", "crypto", "kzg", "trusted_setup.json",
)


def roots_of_unity(n: int = FIELD_ELEMENTS_PER_BLOB) -> list:
    w = pow(PRIMITIVE_ROOT_OF_UNITY, (R - 1) // n, R)
    out = [1]
    for _ in range(n - 1):
        out.append(out[-1] * w % R)
    return out


def bit_reversal_permutation(seq: list) -> list:
    bits = (len(seq) - 1).bit_length()
    return [seq[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(len(seq))]


def versioned_hash(commitment: bytes) -> bytes:
    return VERSIONED_HASH_VERSION_KZG + hashlib.sha256(commitment).digest()[1:]


# --- G1 ----------------------------------------------------------------------


def g1_decompress(data: bytes):
    """Affine point (x, y), or None for the point at infinity."""
    if len(data) != 48 or not data[0] & 0x80:
        raise ValueError("not a compressed G1 point")
    if data[0] & 0x40:
        return None
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    y = pow((x * x * x + 4) % P, (P + 1) // 4, P)
    if y * y % P != (x * x * x + 4) % P:
        raise ValueError("x is not on the curve")
    if (y > (P - 1) // 2) != bool(data[0] & 0x20):
        y = P - y
    return x, y


_INF = (1, 1, 0)


def _jdouble(p):
    X, Y, Z = p
    if Z == 0:
        return p
    A = X * X % P
    B = Y * Y % P
    C = B * B % P
    D = 2 * ((X + B) ** 2 - A - C) % P
    E = 3 * A % P
    X3 = (E * E - 2 * D) % P
    return X3, (E * (D - X3) - 8 * C) % P, 2 * Y * Z % P


def _jadd(p, q):
    if p[2] == 0:
        return q
    if q[2] == 0:
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1, Z2Z2 = Z1 * Z1 % P, Z2 * Z2 % P
    U1, U2 = X1 * Z2Z2 % P, X2 * Z1Z1 % P
    S1, S2 = Y1 * Z2 * Z2Z2 % P, Y2 * Z1 * Z1Z1 % P
    H, r = (U2 - U1) % P, (S2 - S1) % P
    if H == 0:
        return _jdouble(p) if r == 0 else _INF
    HH = H * H % P
    HHH = H * HH % P
    V = U1 * HH % P
    X3 = (r * r - HHH - 2 * V) % P
    return X3, (r * (V - X3) - S1 * HHH) % P, Z1 * Z2 * H % P


def _to_affine(p):
    if p[2] == 0:
        return None
    zi = pow(p[2], -1, P)
    zi2 = zi * zi % P
    return p[0] * zi2 % P, p[1] * zi2 * zi % P


def g1_mul(pt, k: int):
    """k * pt for an affine point, as an affine point (None: infinity)."""
    acc, base = _INF, (pt[0], pt[1], 1)
    for bit in bin(k % R)[2:]:
        acc = _jdouble(acc)
        if bit == "1":
            acc = _jadd(acc, base)
    return _to_affine(acc)


def g1_msm(points: list, scalars: list, window: int = 8):
    """sum scalars[i] * points[i] (affine points), by buckets."""
    bases = [(x, y, 1) for x, y in points]
    total = _INF
    for w in reversed(range(0, 256, window)):
        for _ in range(window):
            total = _jdouble(total)
        buckets = [_INF] * (1 << window)
        for s, b in zip(scalars, bases):
            digit = (s >> w) & ((1 << window) - 1)
            if digit:
                buckets[digit] = _jadd(buckets[digit], b)
        running, acc = _INF, _INF
        for digit in range(len(buckets) - 1, 0, -1):
            running = _jadd(running, buckets[digit])
            acc = _jadd(acc, running)
        total = _jadd(total, acc)
    return _to_affine(total)


def g1_bytes(pt) -> bytes:
    if pt is None:
        return bytes([0xC0]) + bytes(47)
    return bls.g1_compress(pt)


def lagrange_points(path: str = SETUP) -> list:
    """The setup's Lagrange points in natural domain order."""
    with open(path) as f:
        return [g1_decompress(bytes.fromhex(h[2:])) for h in json.load(f)["g1_lagrange"]]


def tau_g1(lagrange: list) -> tuple:
    """[tau]G1 = sum_i w^i L_i(tau) G1."""
    return g1_msm(lagrange, roots_of_unity(len(lagrange)))


# --- the blob pool -------------------------------------------------------------


def blob_from_line(a0: int, a1: int) -> bytes:
    domain = bit_reversal_permutation(roots_of_unity())
    return b"".join(((a0 + a1 * w) % R).to_bytes(32, "big") for w in domain)


def blob_pool(seed: str, count: int, tau: tuple) -> list:
    """`count` (blob, commitment, proof) triples from `seed`."""
    rng = random.Random(seed)
    out = []
    for _ in range(count):
        a0, a1 = rng.randrange(1, R), rng.randrange(1, R)
        commitment = _to_affine(_jadd(
            (*g1_mul(G1, a0), 1), (*g1_mul(tau, a1), 1)
        ))
        out.append((blob_from_line(a0, a1), g1_bytes(commitment), g1_bytes(g1_mul(G1, a1))))
    return out


def proof_valid(blob: bytes, commitment: bytes, proof: bytes, tau: tuple) -> bool:
    """The reference's check of a blob's KZG proof: the blob must be a
    line over the domain (this deployment's blobs are), and then its
    commitment and proof are the unique ones computed from that line."""
    if len(blob) != 32 * FIELD_ELEMENTS_PER_BLOB:
        return False
    evals = [int.from_bytes(blob[i : i + 32], "big") for i in range(0, len(blob), 32)]
    if any(e >= R for e in evals):
        return False
    domain = bit_reversal_permutation(roots_of_unity())
    a1 = (evals[1] - evals[0]) * pow(domain[1] - domain[0], -1, R) % R
    a0 = (evals[0] - a1 * domain[0]) % R
    if any((a0 + a1 * w) % R != e for w, e in zip(domain, evals)):
        raise ValueError("not a blob of this deployment: its polynomial is not a line")
    want = _to_affine(_jadd((*g1_mul(G1, a0), 1), (*g1_mul(tau, a1), 1)))
    return commitment == g1_bytes(want) and proof == g1_bytes(g1_mul(G1, a1))
