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
  Fq-sqrt candidate exponentiations are STACKED into one fixed 379-bit
  square-and-multiply scan (lax.scan over static exponent bits), then
  per-lane selects pick the valid candidate. Non-square inputs yield
  garbage lanes that the SSWU select masks out — exactly one of
  gx1/gx2 is square, so the chosen lane is always exact.
* sgn0 needs canonical (non-Montgomery) parity: one extra mont_mul per
  coordinate converts out of Montgomery form.
* The 3-isogeny constants are taken from the host module (derived there
  via Vélu's formulas, pinned to RFC 9380 §E.3 by tests) and pushed as
  Montgomery limb constants.
* Cofactor clearing reuses `bls381_pairing.g2_clear_cofactor_device`
  (Budroni–Pintore x-ladders + ψ).
"""

from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls12_381 import hash_to_curve as HH
from ..crypto.bls12_381.fields import P
from .bls381 import NLIMB, int_to_limbs, mont_mul, mod_add, mod_sub
from .bls381_pairing import _jit_pt_add_g2, _one_fq2, g2_clear_cofactor_device
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
    fq_inv_staged,
    fq_pow_fixed,
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


@jax.jit
def _jit_sqrt_norm(a):
    x, y = a[..., 0, :], a[..., 1, :]
    return mod_add(mont_mul(x, x), mont_mul(y, y))


@jax.jit
def _jit_sqrt_candidates(a, n):
    """Stack the four Fq-sqrt candidate bases for one shared pow scan."""
    x = a[..., 0, :]
    inv2 = jnp.asarray(_INV2_DEV)
    half_a = mont_mul(mod_add(x, n), inv2)
    half_b = mont_mul(mod_sub(x, n), inv2)
    neg_x = mod_sub(jnp.zeros_like(x), x)
    return jnp.stack([half_a, half_b, x, neg_x], axis=0)


@jax.jit
def _jit_sqrt_pick_t(a, n, roots):
    """Select the valid complex-method candidate; returns (t, 2t)."""
    x = a[..., 0, :]
    inv2 = jnp.asarray(_INV2_DEV)
    half_a = mont_mul(mod_add(x, n), inv2)
    t_a, t_b = roots[0], roots[1]
    ok_a = jnp.all(mont_mul(t_a, t_a) == half_a, axis=-1) & ~_fq_is_zero(t_a)
    t = jnp.where(ok_a[..., None], t_a, t_b)
    return t, mod_add(t, t)


@jax.jit
def _jit_sqrt_finish(a, roots, t, inv_2t):
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


def f2_sqrt_device(a):
    """Batched Fq2 square root (complex method, p ≡ 3 mod 4).

    Returns (root, is_square); non-square lanes yield garbage roots with
    is_square False. Mirrors crypto/bls12_381/fields.py:f2_sqrt. Staged as
    small jits around the shared pow scan — one mega-jit here made XLA-CPU
    compile for hours."""
    norm = _jit_sqrt_norm(a)
    n = fq_pow_fixed(norm, _SQRT_DIGITS)
    roots = fq_pow_fixed(_jit_sqrt_candidates(a, n), _SQRT_DIGITS)
    t, two_t = _jit_sqrt_pick_t(a, n, roots)
    inv_2t = fq_inv_staged(two_t)
    return _jit_sqrt_finish(a, roots, t, inv_2t)


@jax.jit
def _jit_f2_norm(a):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return mod_add(mont_mul(a0, a0), mont_mul(a1, a1))


@jax.jit
def _jit_f2_scale_inv(a, ninv):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return jnp.stack(
        [mont_mul(a0, ninv), mod_sub(jnp.zeros_like(a0), mont_mul(a1, ninv))],
        axis=-2,
    )


def f2_inv_staged(a):
    """Fq2 inversion with the Fq pow hoisted to the shared scan."""
    return _jit_f2_scale_inv(a, fq_inv_staged(_jit_f2_norm(a)))


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


@jax.jit
def _jit_sswu_tv(u):
    z = jnp.asarray(_Z_DEV)
    z_u2 = f2_mul(z, f2_sqr(u))
    tv = f2_add(f2_sqr(z_u2), z_u2)
    return z_u2, tv


@jax.jit
def _jit_sswu_gx(u, z_u2, tv, tv_inv):
    tv_zero = f2_is_zero(tv)
    one = jnp.broadcast_to(jnp.asarray(_ONE_F2_DEV), u.shape).astype(jnp.int32)
    x1_main = f2_mul(jnp.asarray(_MBA_DEV), f2_add(one, tv_inv))
    x1 = f2_select(
        tv_zero,
        jnp.broadcast_to(jnp.asarray(_BZA_DEV), u.shape).astype(jnp.int32),
        x1_main,
    )
    gx1 = _gx(x1)
    x2 = f2_mul(z_u2, x1)
    gx2 = _gx(x2)
    return x1, gx1, x2, gx2


@jax.jit
def _jit_sswu_select(u, x1, x2, roots, is_sq):
    y1, y2 = roots[0], roots[1]
    sq1 = is_sq[0]
    x = f2_select(sq1, x1, x2)
    y = f2_select(sq1, y1, y2)
    flip = f2_sgn0_device(u) != f2_sgn0_device(y)
    y = f2_select(flip, f2_neg(y), y)
    return x, y


def map_to_curve_sswu_device(u):
    """Batched simplified SWU onto E' ([..., 2, 48] → affine (x, y)).
    Staged orchestrator: tv → shared-scan inversion → gx candidates →
    staged sqrt → selects."""
    z_u2, tv = _jit_sswu_tv(u)
    tv_inv = f2_inv_staged(tv)
    x1, gx1, x2, gx2 = _jit_sswu_gx(u, z_u2, tv, tv_inv)
    roots, is_sq = f2_sqrt_device(jnp.stack([gx1, gx2], axis=0))
    return _jit_sswu_select(u, x1, x2, roots, is_sq)


@jax.jit
def _jit_iso(x, y, d_inv):
    """3-isogeny E' → E2 with 1/(x - x0) precomputed (Vélu-derived, RFC
    9380 §E.3-pinned — mirrors the host `_isogeny_to_e2`)."""
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


def isogeny_to_e2_device(x, y):
    d = f2_sub(x, jnp.asarray(_X0_DEV))
    return _jit_iso(x, y, f2_inv_staged(d))


# g2_clear_cofactor_device orchestrates its own staged jits


def hash_to_g2_device(u):
    """Batched hash_to_curve field→group stage.

    u: [n, 2, 2, 48] — per message the two hash_to_field outputs u0, u1
    (Montgomery limbs). Returns Jacobian twisted G2 points ([n, 2, 48]×3)
    in the r-torsion subgroup. Python-level orchestration over staged jits
    (see fq_pow_fixed docstring for why)."""
    u = jnp.asarray(u)
    n = u.shape[0]
    # stack all u0 then all u1 (NOT a raw reshape, which would interleave
    # messages): lanes [0:n] are u0 maps, [n:2n] are u1 maps.
    flat = jnp.concatenate([u[:, 0], u[:, 1]], axis=0)
    x, y = map_to_curve_sswu_device(flat)
    px, py = isogeny_to_e2_device(x, y)
    one2 = _one_fq2((n,))
    # the cofactor-clearing G2 add ([n] lanes) serves this sum too
    s = _jit_pt_add_g2(px[:n], py[:n], one2, px[n:], py[n:], one2)
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
