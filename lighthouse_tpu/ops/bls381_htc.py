"""Device (batched, branchless) SSWU hash-to-G2 for BLS12-381.

The TPU analog of the hash-to-curve inside blst's signature verification
(reference: crypto/bls/src/impls/blst.rs:13 fixes the RFC 9380
BLS12381G2_XMD:SHA-256_SSWU_RO_ ciphersuite; every per-message H(m) in
batch verification runs it). Host keeps only expand_message_xmd — a few
SHA-256 calls over <200-byte inputs per message — and the wide-integer
mod-p reduction; the expensive field work (two SSWU maps with Fq2 square
roots, the 3-isogeny, cofactor clearing) runs on device, vmapped over the
message batch.

Design notes:
* Square roots use the complex method (p ≡ 3 mod 4), mirrored branchlessly
  from the host oracle `crypto/bls12_381/fields.py:f2_sqrt`: all four
  Fq-sqrt candidate exponentiations are STACKED into one pass of the
  fixed-window pow loop (`bls381_tower.fq_pow_digits`), then per-lane
  selects pick the valid candidate. Non-square inputs yield garbage
  lanes that the SSWU select masks out — exactly one of gx1/gx2 is
  square, so the chosen lane is always exact.
* sgn0 needs canonical (non-Montgomery) parity: one extra mont_mul per
  coordinate converts out of Montgomery form.
* The 3-isogeny constants are taken from the host module (derived there
  via Vélu's formulas, pinned to RFC 9380 §E.3 by tests) and pushed as
  Montgomery limb constants.
* Cofactor clearing is `bls381_pairing.g2_clear_cofactor_device`
  (Budroni–Pintore x-ladders + ψ).
* The whole field→group stage is ONE device program
  (`hash_to_g2_device`): every pow chain and ladder stays a loop, traced
  once and never unrolled — an unrolled graph is what made XLA-CPU's LLVM
  stage blow up. SSWU and the isogeny take four pow passes: 1/tv, the
  square roots' two, and one inversion shared by 1/(2t) and the
  isogeny's 1/(x − x0), taken for both x candidates before the select.
"""

from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.bls12_381 import hash_to_curve as HH
from ..crypto.bls12_381.fields import P
from .bls381 import NLIMB, DevFq2, int_to_limbs, mont_mul, mod_add, mod_sub, pt_add
from .bls381_pairing import _one_fq2, g2_clear_cofactor_device
from .bls381_tower import (
    f2_add,
    f2_inv,
    f2_is_zero,
    f2_mul,
    f2_neg,
    f2_select,
    f2_sqr,
    f2_sub,
    fq2_const,
    fq_const,
    f2_norm,
    f2_scale_inv,
    fq_inv,
    fq_pow_digits,
    pow_digits,
)

# --- constants (Montgomery limb form) --------------------------------------

_A_DEV = fq2_const(HH._A)
_B_DEV = fq2_const(HH._B)
_Z_DEV = fq2_const(HH._Z)
_MBA_DEV = fq2_const(HH._MINUS_B_OVER_A)  # -B/A
_BZA_DEV = fq2_const(HH._B_OVER_ZA)  # B/(Z·A)
_X0_DEV = fq2_const(HH._X0)
_T_DEV = fq2_const(HH._T)
_U_DEV = fq2_const(HH._U)
_INV9_DEV = fq2_const(HH._INV9)
_INV27_DEV = fq2_const(HH._INV27)
_INV2_DEV = fq_const((P + 1) // 2)  # 1/2 mod p
_ONE_F2_DEV = fq2_const((1, 0))

_SQRT_DIGITS = pow_digits((P + 1) // 4)




def _fq_is_zero(a):
    return jnp.all(a == 0, axis=-1)


def _sqrt_norm(a):
    x, y = a[..., 0, :], a[..., 1, :]
    return mod_add(mont_mul(x, x), mont_mul(y, y))


def _sqrt_candidates(a, n):
    """Stack the four Fq-sqrt candidate bases for one pow pass."""
    x = a[..., 0, :]
    inv2 = jnp.asarray(_INV2_DEV)
    half_a = mont_mul(mod_add(x, n), inv2)
    half_b = mont_mul(mod_sub(x, n), inv2)
    neg_x = mod_sub(jnp.zeros_like(x), x)
    return jnp.stack([half_a, half_b, x, neg_x], axis=0)


def _sqrt_pick_t(a, n, roots):
    """Select the valid complex-method candidate; returns (t, 2t)."""
    x = a[..., 0, :]
    inv2 = jnp.asarray(_INV2_DEV)
    half_a = mont_mul(mod_add(x, n), inv2)
    t_a, t_b = roots[0], roots[1]
    ok_a = jnp.all(mont_mul(t_a, t_a) == half_a, axis=-1) & ~_fq_is_zero(t_a)
    t = jnp.where(ok_a[..., None], t_a, t_b)
    return t, mod_add(t, t)


def _sqrt_finish(a, roots, t, inv_2t):
    """Assemble (root, is_square) from the candidates + 1/(2t)."""
    x, y = a[..., 0, :], a[..., 1, :]
    zero = jnp.zeros_like(x)
    y_is_zero = _fq_is_zero(y)
    s_x, s_nx = roots[2], roots[3]
    neg_x = mod_sub(zero, x)

    y_over = mont_mul(y, inv_2t)
    root_cplx = jnp.stack([t, y_over], axis=-2)
    sq = f2_sqr(root_cplx)
    cplx_ok = jnp.all(sq == a, axis=(-1, -2))

    ok_sx = jnp.all(mont_mul(s_x, s_x) == x, axis=-1)
    root_y0 = jnp.where(
        ok_sx[..., None, None],
        jnp.stack([s_x, zero], axis=-2),
        jnp.stack([zero, s_nx], axis=-2),
    )
    y0_ok = ok_sx | jnp.all(mont_mul(s_nx, s_nx) == neg_x, axis=-1)

    root = jnp.where(y_is_zero[..., None, None], root_y0, root_cplx)
    is_sq = jnp.where(y_is_zero, y0_ok, cplx_ok)
    a_zero = f2_is_zero(a)
    root = jnp.where(a_zero[..., None, None], jnp.zeros_like(root), root)
    is_sq = is_sq | a_zero
    return root, is_sq


def _sqrt_roots(a):
    """The candidate roots and (t, 2t): two pow passes, the norm's root
    then the four stacked candidates; the root needs 1/(2t) besides."""
    digits = jnp.asarray(_SQRT_DIGITS)
    n = fq_pow_digits(_sqrt_norm(a), digits)
    roots = fq_pow_digits(_sqrt_candidates(a, n), digits)
    return (roots, *_sqrt_pick_t(a, n, roots))


def _from_mont_fq(a):
    one = jnp.zeros_like(a).at[..., 0].set(1)
    return mont_mul(a, one)


def f2_sgn0_device(a):
    """RFC 9380 sgn0 (m=2) over Montgomery limb Fq2: [...,] int32 in {0,1}."""
    c0 = _from_mont_fq(a[..., 0, :])
    c1 = _from_mont_fq(a[..., 1, :])
    s0 = c0[..., 0] & 1
    z0 = jnp.all(c0 == 0, axis=-1).astype(jnp.int32)
    s1 = c1[..., 0] & 1
    return s0 | (z0 & s1)


def _gx(x):
    """g(x) = x³ + A·x + B on E'."""
    a = jnp.asarray(_A_DEV)
    b = jnp.asarray(_B_DEV)
    return f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(a, x)), b)


def map_to_e2(u):
    """Batched simplified SWU onto E', then the 3-isogeny to E2
    ([..., 2, 48] → affine (x, y)) in four pow passes: 1/tv, the two of
    the stacked square root of both gx candidates, then one inversion of
    2t and of both x − x0 candidates, picked by the select after it."""
    z = jnp.asarray(_Z_DEV)
    z_u2 = f2_mul(z, f2_sqr(u))
    tv = f2_add(f2_sqr(z_u2), z_u2)
    tv_inv = f2_inv(tv)

    tv_zero = f2_is_zero(tv)
    one = jnp.broadcast_to(jnp.asarray(_ONE_F2_DEV), u.shape).astype(jnp.int32)
    x1_main = f2_mul(jnp.asarray(_MBA_DEV), f2_add(one, tv_inv))
    x1 = f2_select(
        tv_zero,
        jnp.broadcast_to(jnp.asarray(_BZA_DEV), u.shape).astype(jnp.int32),
        x1_main,
    )
    x2 = f2_mul(z_u2, x1)
    gx = jnp.stack([_gx(x1), _gx(x2)], axis=0)
    roots, t, two_t = _sqrt_roots(gx)
    d = f2_sub(jnp.stack([x1, x2], axis=0), jnp.asarray(_X0_DEV))
    inv = fq_inv(jnp.stack([two_t, f2_norm(d)], axis=0))
    root, is_sq = _sqrt_finish(gx, roots, t, inv[0])

    sq1 = is_sq[0]
    x = f2_select(sq1, x1, x2)
    y = f2_select(sq1, root[0], root[1])
    flip = f2_sgn0_device(u) != f2_sgn0_device(y)
    y = f2_select(flip, f2_neg(y), y)
    d_inv = f2_scale_inv(f2_select(sq1, d[0], d[1]), jnp.where(sq1[..., None], *inv[1]))
    return _isogeny(x, y, d_inv)


def _isogeny(x, y, d_inv):
    """3-isogeny E' → E2 (Vélu-derived, RFC 9380 §E.3-pinned — mirrors the
    host `_isogeny_to_e2`), given d_inv = 1/(x − x0)."""
    d_inv2 = f2_sqr(d_inv)
    d_inv3 = f2_mul(d_inv2, d_inv)
    t = jnp.asarray(_T_DEV)
    u_c = jnp.asarray(_U_DEV)
    phi_x = f2_add(f2_add(x, f2_mul(t, d_inv)), f2_mul(u_c, d_inv2))
    phi_x = f2_mul(phi_x, jnp.asarray(_INV9_DEV))
    one = jnp.broadcast_to(jnp.asarray(_ONE_F2_DEV), x.shape).astype(jnp.int32)
    two_u = f2_add(u_c, u_c)
    deriv = f2_sub(f2_sub(one, f2_mul(t, d_inv2)), f2_mul(two_u, d_inv3))
    phi_y = f2_neg(f2_mul(f2_mul(y, deriv), jnp.asarray(_INV27_DEV)))
    return phi_x, phi_y


@jax.jit
def hash_to_g2_device(u):
    """Batched hash_to_curve field→group stage, one device program.

    u: [n, 2, 2, 48] — per message the two hash_to_field outputs u0, u1
    (Montgomery limbs). Returns Jacobian twisted G2 points ([n, 2, 48]×3)
    in the r-torsion subgroup: SSWU, the isogeny, the sum of the two maps
    and the cofactor clearing, with every pow chain and ladder a loop."""
    n = u.shape[0]
    # stack all u0 then all u1 (NOT a raw reshape, which would interleave
    # messages): lanes [0:n] are u0 maps, [n:2n] are u1 maps.
    flat = jnp.concatenate([u[:, 0], u[:, 1]], axis=0)
    px, py = map_to_e2(flat)
    one2 = _one_fq2((n,))
    s = pt_add(DevFq2, (px[:n], py[:n], one2), (px[n:], py[n:], one2))
    return g2_clear_cofactor_device(s)


def messages_to_field_device(messages, dst: bytes = HH.DST_G2_POP) -> np.ndarray:
    """Host stage: expand_message_xmd + mod-p reduction for a message list →
    [n, 2, 2, 48] Montgomery limb array feeding hash_to_g2_device."""
    from .bls381 import R_MONT

    out = np.zeros((len(messages), 2, 2, NLIMB), dtype=np.int32)
    for i, msg in enumerate(messages):
        u0, u1 = HH.hash_to_field_fq2(msg, 2, dst)
        for j, uval in enumerate((u0, u1)):
            out[i, j, 0] = int_to_limbs(uval[0] * R_MONT % P)
            out[i, j, 1] = int_to_limbs(uval[1] * R_MONT % P)
    return out
