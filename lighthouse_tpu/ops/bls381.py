"""Device BLS12-381 arithmetic: batched field/point ops in JAX.

TPU offload point 1 (SURVEY.md §3.2): the per-set scalar multiplications of
batch signature verification — pubkey scaling by 64-bit random-linear-
combination scalars, signature scaling, and subgroup checks — vectorized
over the batch dimension.

Representation: Fq element = 48 limbs of 8 bits (base 2^8), little-endian,
held in int32. Products of 8-bit limbs are < 2^16 and a 48-term convolution
stays < 2^22 — comfortably inside int32, the widest integer multiply the
TPU VPU has (no u64). Montgomery form with R = 2^384:

    mont_mul(a, b) = a·b·R⁻¹ mod p
      t = conv(a, b)                      (96 limbs, coeffs < 2^22)
      m = low384(t) · N' mod R            (N' = -p⁻¹ mod R, one low-half conv)
      u = (t + m·p) / R                   (one conv + shift)
      conditional subtract p

Fq2 is a pair of Fq lanes; the Jacobian point layer is generic over a field-
ops record, exactly mirroring the host implementation in crypto/bls12_381/
curve.py (which doubles as the correctness oracle in tests).

Everything is shaped [batch, ...limbs] and jit/vmap/shard-friendly: scalar
bits drive a lax.fori_loop of fixed 64/256 trips with branchless selects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.bls12_381.fields import P

NLIMB = 48  # 48 × 8-bit limbs = 384 bits
BASE = 8
MASK = (1 << BASE) - 1
R_MONT = 1 << 384
R2 = (R_MONT * R_MONT) % P
# N' = -p^{-1} mod R (full-width Montgomery constant)
NPRIME = (-pow(P, -1, R_MONT)) % R_MONT

def int_to_limbs(x: int, n: int = NLIMB) -> np.ndarray:
    return np.array([(x >> (BASE * i)) & MASK for i in range(n)], dtype=np.int32)


def ints_to_limbs(values, n: int = NLIMB) -> np.ndarray:
    """[len(values), n] limbs of non-negative ints below 2^(BASE·n): with
    8-bit limbs they are each int's little-endian bytes, converted in one
    pass over their concatenation."""
    assert BASE == 8, "one limb a byte"
    buf = b"".join(v.to_bytes(n, "little") for v in values)
    return np.frombuffer(buf, dtype=np.uint8).reshape(-1, n).astype(np.int32)


def limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(v) << (BASE * i) for i, v in enumerate(arr))


_P_LIMBS = int_to_limbs(P)
_NPRIME_LIMBS = int_to_limbs(NPRIME)
_R2_LIMBS = int_to_limbs(R2)
_ONE_MONT = int_to_limbs(R_MONT % P)  # 1 in Montgomery form
# 2^384 - p (for branchless compare/subtract via complement addition)
_PBAR_LIMBS = int_to_limbs(R_MONT - P)


# ---------------------------------------------------------------------------
# Limb-vector primitives (shapes [..., NLIMB], int32)
# ---------------------------------------------------------------------------


def _conv(a, b, out_cols: int):
    """Limb convolution c_k = Σ_i a_i·b_(k−i) for k < out_cols, as N
    multiply-adds of a limb of `a` against a shifted copy of `b` (products
    < 2^16, sums < N·2^16: exact in int32). On a v5e this beats summing
    the [N, N] outer product's anti-diagonals by an int32 matmul against a
    0/1 matrix (int32 is not an MXU type there): a [512, 48] Montgomery
    product took 12.8 µs with all three of its convolutions done this way,
    50.1 µs with all three by the 0/1 matmul (one TPU v5e chip). `_mont_mul`
    uses it for the variable × variable product only; its two products by
    a constant go through `_conv_const`."""
    n = a.shape[-1]
    bp = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(n - 1, out_cols)])
    acc = a[..., :1] * bp[..., n - 1 : n - 1 + out_cols]
    for i in range(1, n):
        acc = acc + a[..., i : i + 1] * bp[..., n - 1 - i : n - 1 - i + out_cols]
    return acc


@functools.cache
def _toeplitz(limbs: tuple, out_cols: int) -> np.ndarray:
    """[N, out_cols] int32 T with T[i, i+j] = limbs[j]: x @ T convolves x
    with the constant limbs, truncated to out_cols (a mod-2^(8·out_cols)
    product when out_cols ≤ N). Cached as numpy — a jnp constant cached
    across traces would leak tracers."""
    n = len(limbs)
    t = np.zeros((n, out_cols), dtype=np.int32)
    for i in range(n):
        for j, v in enumerate(limbs):
            if i + j < out_cols:
                t[i, i + j] = v
    return t


def _conv_const(x, limbs: np.ndarray, out_cols: int):
    """Convolution with a constant operand: one small int32 matmul."""
    return x @ jnp.asarray(_toeplitz(tuple(int(v) for v in limbs), out_cols))


def _shift_carries(v):
    """One vectorized carry pass: keep low 8 bits, push carries one limb up
    (carry out of the last limb must be provably zero at every call site)."""
    hi = v >> BASE
    return (v & MASK) + jnp.concatenate(
        [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1
    )


_CHUNK = 24  # limb carry bits packed per int32 word (sums stay < 2^25)


def _resolve_carries(v):
    """Exact normalization for limbs in [0, 256] by one integer addition.

    A limb generates a carry when it is 256 and propagates one when it is
    255: the carry pattern of the binary sum X + Y with x_i = g_i | p_i and
    y_i = g_i. The limb bits are packed 24 to an int32 word, the words are
    added with their carries rippled word to word, and (X + Y) ^ X ^ Y
    unpacks to the carry into every limb — a chain of 255s resolves in a
    constant number of ops (no log-depth scan, no O(n) ripple)."""
    n = v.shape[-1]
    k = -(-n // _CHUNK)
    g = v >> BASE
    x = g | (v == MASK).astype(v.dtype)
    pad = [(0, 0)] * (v.ndim - 1) + [(0, k * _CHUNK - n)]
    shifts = jnp.arange(_CHUNK, dtype=v.dtype)

    def pack(bits):
        bits = jnp.pad(bits, pad).reshape(*v.shape[:-1], k, _CHUNK)
        return jnp.sum(bits << shifts, axis=-1)

    xw, yw = pack(x), pack(g)
    sums = []
    cin = jnp.zeros_like(xw[..., 0])
    for c in range(k):
        s = xw[..., c] + yw[..., c] + cin
        sums.append(s)
        cin = s >> _CHUNK
    carries = jnp.stack(sums, axis=-1) ^ xw ^ yw
    carry_in = (carries[..., None] >> shifts) & 1
    carry_in = carry_in.reshape(*v.shape[:-1], k * _CHUNK)[..., :n]
    return (v + carry_in) & MASK


def _carry_normalize(x, out_len: int, shrink_passes: int = 3):
    """Canonical 8-bit limbs from bounded coefficients (< 2^22): a few
    ripple passes shrink limbs into [0, 256], then one exact lookahead
    resolve."""
    n = x.shape[-1]
    if n < out_len:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, out_len - n)]
        x = jnp.pad(x, pad)
    elif n > out_len:
        raise ValueError("carry overflow: input longer than output")
    v = x
    for _ in range(shrink_passes):
        v = _shift_carries(v)
    return _resolve_carries(v)


def _cond_sub(x, mbar):
    """x normalized, value in [0, 2m) → x mod m, branchless: s = x + (2^(8n)
    - m); bit 8n of s is set iff x ≥ m, and then s's low limbs are x - m."""
    n = x.shape[-1]
    s = _carry_normalize(x + mbar, n + 1, shrink_passes=2)  # limbs ≤ 510
    return jnp.where(s[..., n:] > 0, s[..., :n], x)


def _mont_mul(a, b, m_limbs, nprime_limbs, mbar):
    """Montgomery product a·b·R⁻¹ mod m over n-limb operands (R = 2^(8n)):
    t = a·b; q = (t mod R)·N' mod R; u = (t + q·m)/R; conditional subtract.
    t + q·m is normalized once from the raw convolution coefficients
    (each < n·2^16, their sum < 2^23), and only t's low half is normalized
    separately, to feed q."""
    n = a.shape[-1]
    t = _conv(a, b, 2 * n - 1)  # [..., 2n-1], coeffs < n·2^16
    t_lo = _carry_normalize(t[..., :n], n)  # t mod R
    q = _carry_normalize(_conv_const(t_lo, nprime_limbs, n), n)  # mod R
    # t + q·m < 2Rm: fits 2n limbs; its low n limbs are zero by
    # construction of q, so /R is a limb shift.
    s = _carry_normalize(t + _conv_const(q, m_limbs, 2 * n - 1), 2 * n)
    return _cond_sub(s[..., n:], mbar)


def _mod_add(a, b, mbar):
    """(a + b) mod m: both candidates, a+b and a+b+(2^(8n)-m), normalized
    in one stacked pass; bit 8n of the second says which one is reduced."""
    n = a.shape[-1]
    x = a + b
    v = _carry_normalize(jnp.stack([x, x + mbar]), n + 1, shrink_passes=2)
    return jnp.where(v[1, ..., n:] > 0, v[1, ..., :n], v[0, ..., :n])


def _mod_sub(a, b, unit, unit_plus_m):
    """(a − b) mod m via the complement: d = a + (2^(8n)−1−b) + 1 =
    a − b + 2^(8n). Both d and d + m are normalized in one stacked pass;
    bit 8n of d is set iff a ≥ b, and then d's low limbs are a − b."""
    n = a.shape[-1]
    d = a + (MASK - b)
    v = _carry_normalize(
        jnp.stack([d + unit, d + unit_plus_m]), n + 1, shrink_passes=2
    )
    return jnp.where(v[0, ..., n:] > 0, v[0, ..., :n], v[1, ..., :n])


_UNIT = int_to_limbs(1)
_UNIT_PLUS_P = _UNIT + _P_LIMBS  # limbwise; normalized with the sum


@jax.jit
def mont_mul(a, b):
    """Montgomery product a·b·R⁻¹ mod p. Inputs/outputs: [..., 48] int32,
    limbs < 2^8, value < p. Jitted, like the point ops below: a program
    that holds hundreds of them traces each once a shape, and XLA inlines
    the calls, so the device code is the same."""
    return _mont_mul(a, b, _P_LIMBS, _NPRIME_LIMBS, jnp.asarray(_PBAR_LIMBS))


def to_mont(x_limbs):
    return mont_mul(x_limbs, jnp.asarray(_R2_LIMBS))


def from_mont(x_limbs):
    one = jnp.zeros_like(x_limbs).at[..., 0].set(1)
    return mont_mul(x_limbs, one)


def mod_add(a, b):
    return _mod_add(a, b, jnp.asarray(_PBAR_LIMBS))


def mod_sub(a, b):
    return _mod_sub(a, b, jnp.asarray(_UNIT), jnp.asarray(_UNIT_PLUS_P))


# ---------------------------------------------------------------------------
# Field-ops records (device analog of crypto/bls12_381/curve.py FieldOps)
# ---------------------------------------------------------------------------


class DevFq:
    """Fq ops over [..., 48] limb arrays (values in Montgomery form)."""

    @staticmethod
    def add(a, b):
        return mod_add(a, b)

    @staticmethod
    def sub(a, b):
        return mod_sub(a, b)

    @staticmethod
    def mul(a, b):
        return mont_mul(a, b)

    @staticmethod
    def sqr(a):
        return mont_mul(a, a)

    @staticmethod
    def neg(a):
        zero = jnp.zeros_like(a)
        return mod_sub(zero, a)

    @staticmethod
    def zeros(shape):
        return jnp.zeros((*shape, NLIMB), dtype=jnp.int32)

    @staticmethod
    def is_zero(a):
        return jnp.all(a == 0, axis=-1)

    @staticmethod
    def select(c, a, b):
        """c: [...] bool — where(c, a, b) broadcast over limbs."""
        return jnp.where(c[..., None], a, b)


class DevFq2:
    """Fq2 ops over [..., 2, 48] limb arrays (c0 + c1·u, u² = -1). Each op
    works on both coefficients at once: one mont_mul per product (the
    three Karatsuba products ride a stacked axis), one mod_add/mod_sub per
    sum — a few large ops instead of many small ones keeps the compiled
    program small."""

    @staticmethod
    def add(a, b):
        return mod_add(a, b)

    @staticmethod
    def sub(a, b):
        return mod_sub(a, b)

    @staticmethod
    @jax.jit
    def mul(a, b):
        a, b = jnp.broadcast_arrays(a, b)
        a0, a1 = a[..., 0, :], a[..., 1, :]
        b0, b1 = b[..., 0, :], b[..., 1, :]
        sums = mod_add(jnp.stack([a0, b0], -2), jnp.stack([a1, b1], -2))
        t = mont_mul(
            jnp.stack([a0, a1, sums[..., 0, :]], -2),
            jnp.stack([b0, b1, sums[..., 1, :]], -2),
        )
        t0, t1, cross = t[..., 0, :], t[..., 1, :], t[..., 2, :]
        d = mod_sub(jnp.stack([t0, cross], -2), jnp.stack([t1, t0], -2))
        c1 = mod_sub(d[..., 1, :], t1)
        return jnp.stack([d[..., 0, :], c1], -2)

    @staticmethod
    @jax.jit
    def sqr(a):
        """(a0+a1)(a0−a1) + 2·a0·a1·u."""
        a0, a1 = a[..., 0, :], a[..., 1, :]
        t = mont_mul(
            jnp.stack([mod_add(a0, a1), a0], -2),
            jnp.stack([mod_sub(a0, a1), a1], -2),
        )
        return jnp.stack([t[..., 0, :], mod_add(t[..., 1, :], t[..., 1, :])], -2)

    @staticmethod
    def neg(a):
        return mod_sub(jnp.zeros_like(a), a)

    @staticmethod
    def zeros(shape):
        return jnp.zeros((*shape, 2, NLIMB), dtype=jnp.int32)

    @staticmethod
    def is_zero(a):
        return jnp.all(a == 0, axis=(-1, -2))

    @staticmethod
    def select(c, a, b):
        return jnp.where(c[..., None, None], a, b)


def _stack(*xs):
    return jnp.stack(jnp.broadcast_arrays(*xs), axis=0)


# ---------------------------------------------------------------------------
# Generic Jacobian point ops (branchless; infinity encoded as Z == 0)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0)
def pt_double(F, pt):
    """Jacobian doubling (dbl-2009-l). Independent products share one
    stacked F.mul call: three multiplication stages in all."""
    x, y, z = pt
    a, b, yz = F.mul(_stack(x, y, y), _stack(x, y, z))
    xb = F.add(x, b)
    e = F.add(F.add(a, a), a)
    c, t, f = F.mul(_stack(b, xb, e), _stack(b, xb, e))
    d = F.sub(F.sub(t, a), c)
    d = F.add(d, d)
    x3 = F.sub(f, F.add(d, d))
    c8 = F.add(F.add(c, c), F.add(c, c))
    c8 = F.add(c8, c8)
    y3 = F.sub(F.mul(e, F.sub(d, x3)), c8)
    z3 = F.add(yz, yz)
    return (x3, y3, z3)


@functools.partial(jax.jit, static_argnums=0)
def pt_add(F, p1, p2):
    """Branchless Jacobian add handling infinity and doubling cases via
    selects (device code cannot branch per lane). Independent products
    share one stacked F.mul call: five multiplication stages, plus the
    doubling candidate."""
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    inf1 = F.is_zero(z1)
    inf2 = F.is_zero(z2)
    z1z1, z2z2 = F.mul(_stack(z1, z2), _stack(z1, z2))
    u1, u2, t2, t1 = F.mul(_stack(x1, x2, z2z2, z1z1), _stack(z2z2, z1z1, z2, z1))
    s1, s2, z1z2 = F.mul(_stack(y1, y2, z1), _stack(t2, t1, z2))
    h, r = F.sub(_stack(u2, s2), _stack(u1, s1))
    same_x = F.is_zero(h)
    same_y = F.is_zero(r)
    is_double = same_x & same_y & ~inf1 & ~inf2
    is_inf_result = same_x & ~same_y & ~inf1 & ~inf2

    h2, r2 = F.add(_stack(h, r), _stack(h, r))
    i, r2sq = F.mul(_stack(h2, r2), _stack(h2, r2))
    j, v, z3 = F.mul(_stack(h, u1, z1z2), _stack(i, i, h))
    x3 = F.sub(F.sub(r2sq, j), F.add(v, v))
    m, s1j = F.mul(_stack(r2, s1), _stack(F.sub(v, x3), j))
    y3 = F.sub(m, F.add(s1j, s1j))
    z3 = F.add(z3, z3)

    dx, dy, dz = pt_double(F, p1)

    x3 = F.select(is_double, dx, x3)
    y3 = F.select(is_double, dy, y3)
    z3 = F.select(is_double, dz, z3)

    zero = F.zeros(z3.shape[: z3.ndim - (1 if F is DevFq else 2)])
    z3 = F.select(is_inf_result, zero, z3)

    # infinity inputs pass the other operand through
    x3 = F.select(inf1, x2, x3)
    y3 = F.select(inf1, y2, y3)
    z3 = F.select(inf1, z2, z3)
    x3 = F.select(inf2 & ~inf1, x1, x3)
    y3 = F.select(inf2 & ~inf1, y1, y3)
    z3 = F.select(inf2 & ~inf1, z1, z3)
    return (x3, y3, z3)


def pt_scalar_mul(F, pt, scalar_bits, start=None):
    """Batch double-and-add: scalar_bits [batch, nbits] int32 (LSB first),
    pt = tuple of [batch, ...] coords. Fixed trip count, branchless.
    Returns start + [k]pt, start infinity unless given."""
    nbits = scalar_bits.shape[-1]

    def body(i, carry):
        acc, addend = carry
        bit = scalar_bits[..., i]
        added = pt_add(F, acc, addend)
        acc = tuple(
            F.select(bit.astype(bool), a_new, a_old)
            for a_new, a_old in zip(added, acc)
        )
        addend = pt_double(F, addend)
        return (acc, addend)

    batch_shape = scalar_bits.shape[:-1]
    zero = F.zeros(batch_shape)
    one_mont = jnp.broadcast_to(
        jnp.asarray(_ONE_MONT), (*batch_shape, NLIMB)
    ).astype(jnp.int32)
    if F is DevFq2:
        one = jnp.stack([one_mont, jnp.zeros_like(one_mont)], axis=-2)
    else:
        one = one_mont
    acc = (one, one, zero) if start is None else start
    acc, _ = lax.fori_loop(0, nbits, body, (acc, pt))
    return acc


def fold_sum(F, acc, pt):
    """acc [n] + Σ_j pt[:, j] (coords [n, k, ...], infinity pads) as one
    fori_loop of single adds: one point add in the compiled program for
    any k (a scanned add tree compiles several times slower on TPU)."""

    def body(j, acc):
        return pt_add(F, acc, tuple(c[:, j] for c in pt))

    return lax.fori_loop(0, pt[0].shape[1], body, acc)


# ---------------------------------------------------------------------------
# Host <-> device conversion
# ---------------------------------------------------------------------------


def fq_to_device(values: list[int]) -> np.ndarray:
    """List of field ints → [batch, 48] Montgomery limb array."""
    return ints_to_limbs([v * R_MONT % P for v in values])


def fq_from_device(arr) -> list[int]:
    out = []
    host = np.asarray(arr)
    for row in host.reshape(-1, NLIMB):
        out.append(limbs_to_int(row) * pow(R_MONT, -1, P) % P)
    return out


def g1_points_to_device(points) -> tuple:
    """Host Jacobian G1 points (int tuples) → device limb arrays [n,48]×3."""
    xs, ys, zs = [], [], []
    for (x, y, z) in points:
        xs.append(x)
        ys.append(y)
        zs.append(z)
    return (
        jnp.asarray(fq_to_device(xs)),
        jnp.asarray(fq_to_device(ys)),
        jnp.asarray(fq_to_device(zs)),
    )


def g1_points_from_device(pt) -> list:
    xs = fq_from_device(pt[0])
    ys = fq_from_device(pt[1])
    zs = fq_from_device(pt[2])
    return list(zip(xs, ys, zs))


def g2_points_to_device(points) -> tuple:
    coords = [[], [], []]
    for p in points:
        for k in range(3):
            coords[k].append(p[k])
    out = []
    for lane in coords:
        c0 = fq_to_device([c[0] for c in lane])
        c1 = fq_to_device([c[1] for c in lane])
        out.append(jnp.asarray(np.stack([c0, c1], axis=1)))
    return tuple(out)


def g2_points_from_device(pt) -> list:
    out = []
    host = [np.asarray(c) for c in pt]
    n = host[0].shape[0]
    rinv = pow(R_MONT, -1, P)
    for i in range(n):
        coords = []
        for k in range(3):
            c0 = limbs_to_int(host[k][i, 0]) * rinv % P
            c1 = limbs_to_int(host[k][i, 1]) * rinv % P
            coords.append((c0, c1))
        out.append(tuple(coords))
    return out


def scalars_to_bits(scalars: list[int], nbits: int) -> np.ndarray:
    out = np.zeros((len(scalars), nbits), dtype=np.int32)
    for i, s in enumerate(scalars):
        for b in range(nbits):
            out[i, b] = (s >> b) & 1
    return out


# ---------------------------------------------------------------------------
# Jitted batch kernels
# ---------------------------------------------------------------------------


@jax.jit
def batch_g1_scalar_mul(xs, ys, zs, bits):
    """[n] G1 points × [n, nbits] scalars → [n] G1 points (Jacobian)."""
    return pt_scalar_mul(DevFq, (xs, ys, zs), bits)


@jax.jit
def batch_g2_scalar_mul(xs, ys, zs, bits):
    return pt_scalar_mul(DevFq2, (xs, ys, zs), bits)
