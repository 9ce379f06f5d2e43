"""Device Fq2/Fq6/Fq12 tower arithmetic for BLS12-381 pairings.

Extends the batched limb kernels of `ops.bls381` (Fq/Fq2 over [..., 48]
int32 Montgomery limbs) up the tower used by the optimal-ate pairing:

    Fq2  = Fq[u]/(u²+1)          shape [..., 2, 48]
    Fq6  = Fq2[v]/(v³−ξ), ξ=u+1  shape [..., 3, 2, 48]
    Fq12 = Fq6[w]/(w²−v)         shape [..., 2, 3, 2, 48]

All values are in Montgomery form. The formulas mirror the host tower in
`crypto/bls12_381/fields.py` (the correctness oracle in tests) — Karatsuba
Fq2/Fq6/Fq12 multiplication, tower inversion reduced to one Fq inversion
(done by Fermat with a fixed 381-bit square-and-multiply scan; device code
cannot use extended Euclid's data-dependent loop), and Frobenius via
host-precomputed γ coefficients pushed as Montgomery limb constants.

Role in the reference: these are the Fq12 field ops inside blst's pairing
(vendored C/assembly, crypto/bls/src/impls/blst.rs:112) — here batched over
the signature-set dimension and jit/shard-friendly.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.bls12_381 import fields as HF
from ..crypto.bls12_381.fields import P
from .bls381 import (
    NLIMB,
    R_MONT,
    DevFq2,
    _ONE_MONT,
    int_to_limbs,
    mod_add,
    mod_sub,
    mont_mul,
)

# ---------------------------------------------------------------------------
# Constants (host ints → Montgomery limb arrays)
# ---------------------------------------------------------------------------


def fq_const(v: int) -> np.ndarray:
    """Fq constant in Montgomery limb form, shape [48]."""
    return int_to_limbs(v * R_MONT % P)


def fq2_const(c) -> np.ndarray:
    """Fq2 constant (c0, c1) → [2, 48] Montgomery limbs."""
    return np.stack([fq_const(c[0]), fq_const(c[1])])


_FQ_ZERO = np.zeros(NLIMB, dtype=np.int32)
F2_ONE_DEV = np.stack([_ONE_MONT, _FQ_ZERO])
F2_ZERO_DEV = np.zeros((2, NLIMB), dtype=np.int32)

# Frobenius coefficients (derived on host in fields.py, not memorized):
#   v^p  = γ6_1·v,  v^{2p} = γ6_2·v²,  w^p = γ12·w
_G6_1_DEV = fq2_const(HF._G6_1)
_G6_2_DEV = fq2_const(HF._G6_2)
_G12_DEV = fq2_const(HF._G12)

_POW_WINDOW = 4
_POW_DIGITS = 384 // _POW_WINDOW  # every Fq exponent padded to 384 bits


def pow_digits(e: int) -> np.ndarray:
    """MSB-first 4-bit digits of e, padded to the shared 384-bit width."""
    return np.array(
        [(e >> (_POW_WINDOW * i)) & 0xF for i in reversed(range(_POW_DIGITS))],
        dtype=np.int32,
    )


_PM2_DIGITS = pow_digits(P - 2)


# ---------------------------------------------------------------------------
# Fq2 ops ([..., 2, 48]); complements ops.bls381.DevFq2
# ---------------------------------------------------------------------------


f2_add = DevFq2.add
f2_sub = DevFq2.sub
f2_neg = DevFq2.neg
f2_mul = DevFq2.mul
f2_sqr = DevFq2.sqr


def f2_conj(a):
    c1 = mod_sub(jnp.zeros_like(a[..., 1, :]), a[..., 1, :])
    return jnp.stack([a[..., 0, :], c1], axis=-2)


def f2_mul_xi(a):
    """ξ·a = (c0−c1) + (c0+c1)u."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return jnp.stack([mod_sub(a0, a1), mod_add(a0, a1)], axis=-2)


def f2_double(a):
    return mod_add(a, a)


def f2_triple(a):
    return mod_add(mod_add(a, a), a)


def fq_pow_digits(a, digits):
    """a^e over [..., 48] limbs, e as `pow_digits(e)` (a runtime array):
    fixed 4-bit-window exponentiation — a table of a^0..a^15, then per
    digit four squarings and one table product, 96 loop steps for a
    384-bit exponent (no data-dependent control flow)."""
    one = jnp.broadcast_to(jnp.asarray(_ONE_MONT), a.shape).astype(jnp.int32)
    table = [one, a]
    while len(table) < 1 << _POW_WINDOW:
        m = len(table)
        top = mont_mul(table[m // 2], table[m // 2])  # a^m
        table += [top, *mont_mul(top, jnp.stack(table[1:m]))]
    table = jnp.stack(table)

    def body(i, acc):
        for _ in range(_POW_WINDOW):
            acc = mont_mul(acc, acc)
        return mont_mul(acc, lax.dynamic_index_in_dim(table, digits[i], keepdims=False))

    return lax.fori_loop(0, digits.shape[0], body, one)


def fq_inv(a):
    """Fermat a^(p−2) over [..., 48] limbs."""
    return fq_pow_digits(a, jnp.asarray(_PM2_DIGITS))


#: lanes per shared pow call: the largest caller (four sqrt candidates of
#: two SSWU maps over 2×32 messages) fills it; smaller ones pad up, so
#: every exponent and every caller runs ONE compiled loop
POW_LANES = 512


def fq_pow_fixed(a, digits_np: np.ndarray):
    """a^e over [..., 48] Montgomery limbs, exponent as `pow_digits(e)`.
    The digits ride as a RUNTIME argument and the lanes pad to a multiple
    of POW_LANES, so one jitted loop serves every exponent and shape —
    sqrt chains, Fermat inversions, the lot."""
    flat = a.reshape(-1, a.shape[-1])
    n = flat.shape[0]
    pad = -(-n // POW_LANES) * POW_LANES - n
    flat = jnp.concatenate([flat, jnp.zeros((pad, flat.shape[1]), flat.dtype)])
    out = _fq_pow_var(flat, jnp.asarray(digits_np))
    return out[:n].reshape(a.shape)


_fq_pow_var = jax.jit(fq_pow_digits)


def fq_inv_staged(a):
    """1/a over Fq limbs via the shared pow loop."""
    return fq_pow_fixed(a, _PM2_DIGITS)


def f2_inv(a):
    """1/(a0+a1u) = (a0 − a1u)/(a0²+a1²): one Fq inversion."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    norm = mod_add(mont_mul(a0, a0), mont_mul(a1, a1))
    ninv = fq_inv(norm)
    return jnp.stack(
        [mont_mul(a0, ninv), mod_sub(jnp.zeros_like(a0), mont_mul(a1, ninv))],
        axis=-2,
    )


def f2_select(c, a, b):
    """c: [...] bool."""
    return jnp.where(c[..., None, None], a, b)


def f2_is_zero(a):
    return jnp.all(a == 0, axis=(-1, -2))


# ---------------------------------------------------------------------------
# Fq6 ops ([..., 3, 2, 48])
# ---------------------------------------------------------------------------


def _f6(c0, c1, c2):
    return jnp.stack([c0, c1, c2], axis=-3)


def f6_slots(a):
    return a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]


def f6_add(a, b):
    return mod_add(a, b)


def f6_sub(a, b):
    return mod_sub(a, b)


def f6_neg(a):
    return mod_sub(jnp.zeros_like(a), a)


def _st(*xs, axis=-3):
    return jnp.stack(jnp.broadcast_arrays(*xs), axis=axis)


def f6_mul(a, b):
    """Toom-style 6-mult Fq6 product (mirrors host f6_mul); the six Fq2
    products are independent, so they ride one stacked f2_mul."""
    a, b = jnp.broadcast_arrays(a, b)
    a0, a1, a2 = f6_slots(a)
    b0, b1, b2 = f6_slots(b)
    sa = f2_add(_st(a0, a1, a0), _st(a1, a2, a2))  # a0+a1, a1+a2, a0+a2
    sb = f2_add(_st(b0, b1, b0), _st(b1, b2, b2))
    t = f2_mul(jnp.concatenate([a, sa], -3), jnp.concatenate([b, sb], -3))
    t0, t1, t2, x01, x12, x02 = (t[..., i, :, :] for i in range(6))
    d = f2_sub(_st(x12, x01, x02), f2_add(_st(t1, t0, t0), _st(t2, t1, t2)))
    xi = f2_mul_xi(_st(d[..., 0, :, :], t2))
    return f2_add(
        _st(t0, d[..., 1, :, :], d[..., 2, :, :]),
        _st(xi[..., 0, :, :], xi[..., 1, :, :], t1),
    )


def f6_sqr(a):
    return f6_mul(a, a)


def f6_mul_by_v(a):
    a0, a1, a2 = f6_slots(a)
    return _f6(f2_mul_xi(a2), a0, a1)


def f6_inv_parts(a):
    """a⁻¹ = c / d over Fq6: the adjugate c [..., 3, 2, 48] and the Fq2
    denominator d."""
    a0, a1, a2 = f6_slots(a)
    c0 = f2_sub(f2_sqr(a0), f2_mul_xi(f2_mul(a1, a2)))
    c1 = f2_sub(f2_mul_xi(f2_sqr(a2)), f2_mul(a0, a1))
    c2 = f2_sub(f2_sqr(a1), f2_mul(a0, a2))
    denom = f2_add(
        f2_mul(a0, c0), f2_mul_xi(f2_add(f2_mul(a2, c1), f2_mul(a1, c2)))
    )
    return _f6(c0, c1, c2), denom


def f6_inv(a):
    c, denom = f6_inv_parts(a)
    return f2_mul(c, f2_inv(denom)[..., None, :, :])


def f6_frob(a):
    c = f2_conj(a)
    g = jnp.asarray(np.stack([_G6_1_DEV, _G6_2_DEV]))
    return jnp.concatenate([c[..., :1, :, :], f2_mul(c[..., 1:, :, :], g)], -3)


# ---------------------------------------------------------------------------
# Fq12 ops ([..., 2, 3, 2, 48])
# ---------------------------------------------------------------------------


def _f12(a, b):
    return jnp.stack([a, b], axis=-4)


def f12_slots(a):
    return a[..., 0, :, :, :], a[..., 1, :, :, :]


def f12_ones(batch_shape) -> jnp.ndarray:
    one = np.zeros((2, 3, 2, NLIMB), dtype=np.int32)
    one[0, 0] = F2_ONE_DEV
    return jnp.broadcast_to(jnp.asarray(one), (*batch_shape, 2, 3, 2, NLIMB))


def f12_add(a, b):
    return mod_add(a, b)


def f12_mul(a, b):
    """Karatsuba over Fq6: the three Fq6 products ride one stacked f6_mul."""
    a, b = jnp.broadcast_arrays(a, b)
    a0, a1 = f12_slots(a)
    b0, b1 = f12_slots(b)
    t = f6_mul(
        jnp.stack([a0, a1, f6_add(a0, a1)], -4),
        jnp.stack([b0, b1, f6_add(b0, b1)], -4),
    )
    t0, t1, x = t[..., 0, :, :, :], t[..., 1, :, :, :], t[..., 2, :, :, :]
    s = f6_add(jnp.stack([t0, t0], -4), jnp.stack([f6_mul_by_v(t1), t1], -4))
    return _f12(s[..., 0, :, :, :], f6_sub(x, s[..., 1, :, :, :]))


def f12_sqr(a):
    """a·a through f12_mul: the dedicated squaring formula saves a third
    of the Fq6 products, but its program takes the TPU compiler over twice
    as long, and at these lane counts the products are not the cost."""
    return f12_mul(a, a)


def f12_conj(a):
    a0, a1 = f12_slots(a)
    return _f12(a0, f6_neg(a1))


def f12_inv(a):
    a0, a1 = f12_slots(a)
    t = f6_inv(f6_sub(f6_sqr(a0), f6_mul_by_v(f6_sqr(a1))))
    return _f12(f6_mul(a0, t), f6_neg(f6_mul(a1, t)))


def f12_frob(a):
    b = f6_frob(a)
    b1 = f2_mul(b[..., 1, :, :, :], jnp.asarray(_G12_DEV))
    return _f12(b[..., 0, :, :, :], b1)


# ---------------------------------------------------------------------------
# Host <-> device conversion for tower elements
# ---------------------------------------------------------------------------


def f2_to_device(vals: list) -> np.ndarray:
    """List of host Fq2 tuples → [n, 2, 48]."""
    return np.stack([fq2_const(v) for v in vals]).astype(np.int32)


def f12_to_device(vals: list) -> np.ndarray:
    """List of host Fq12 tuples → [n, 2, 3, 2, 48]."""
    out = np.zeros((len(vals), 2, 3, 2, NLIMB), dtype=np.int32)
    for i, (lo, hi) in enumerate(vals):
        for w, part in enumerate((lo, hi)):
            for v, c in enumerate(part):
                out[i, w, v] = fq2_const(c)
    return out


def f12_from_device(arr) -> list:
    """[..., 2, 3, 2, 48] Montgomery limbs → list of host Fq12 tuples: one
    bytes → int per Fq and one product by R⁻¹ mod p. Device limbs are
    bytes: every field kernel normalizes its limbs into [0, 256)."""
    raw = np.asarray(arr).astype(np.uint8).tobytes()
    rinv = pow(R_MONT, -1, P)
    fq = [
        int.from_bytes(raw[i : i + NLIMB], "little") * rinv % P
        for i in range(0, len(raw), NLIMB)
    ]
    f2 = list(zip(fq[0::2], fq[1::2]))
    f6 = list(zip(f2[0::3], f2[1::3], f2[2::3]))
    return list(zip(f6[0::2], f6[1::2]))
