"""Device arithmetic over the BLS12-381 scalar field Fr (r ≈ 2^255).

The KZG evaluation-form hot loops are Fr-heavy: the barycentric formula
evaluates p(z) as a 4096-term Σ pᵢ·wᵢ/(z−wᵢ) (crypto/kzg/src/lib.rs wraps
c-kzg, which does this in C; polynomial-commitments.md `evaluate_polynomial_
in_evaluation_form`). Per-term modular inversions make this the dominant
cost of blob verification on the host (4096 Fermat pows per blob), and it
is embarrassingly parallel — exactly the shape the TPU VPU wants.

Representation mirrors ops/bls381.py: 32 little-endian 8-bit limbs in
int32 (256 bits ≥ 255-bit r), Montgomery form with R = 2^256. The generic
convolution/carry helpers are shared with the Fq implementation; only the
modulus constants differ.

Kernels:
  * fr_mul / fr_add / fr_sub           — [..., 32] lanewise field ops
  * fr_inv                             — Fermat a^(r−2), vectorized fori
  * barycentric_eval_batch             — y_j = p_j(z_j) for a batch of
    blobs over the shared bit-reversed domain: one fused kernel
  * quotient_batch                     — qᵢ = (pᵢ−y)·(wᵢ−z)⁻¹ for device
    proof computation (compute_kzg_proof pointwise quotient)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.bls12_381.fields import R as FR_MOD
from .bls381 import (
    BASE,
    MASK,
    _mod_add,
    _mod_sub,
    _mont_mul,
    ints_to_limbs,
)

NLIMB_FR = 32  # 32 × 8-bit limbs = 256 bits
R_MONT_FR = 1 << 256
R2_FR = (R_MONT_FR * R_MONT_FR) % FR_MOD
NPRIME_FR = (-pow(FR_MOD, -1, R_MONT_FR)) % R_MONT_FR


def _int_to_limbs(x: int, n: int = NLIMB_FR) -> np.ndarray:
    return np.array([(x >> (BASE * i)) & MASK for i in range(n)], dtype=np.int32)


def _limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(v) << (BASE * i) for i, v in enumerate(arr))


_R_LIMBS = _int_to_limbs(FR_MOD)
_NPRIME_LIMBS = _int_to_limbs(NPRIME_FR)
_R2_LIMBS = _int_to_limbs(R2_FR)
_ONE_MONT = _int_to_limbs(R_MONT_FR % FR_MOD)
# 2^256 - r, for the branchless conditional subtract
_RBAR_LIMBS = _int_to_limbs(R_MONT_FR - FR_MOD)
# Fermat exponent r-2, LSB-first bits (static constant; 255 bits)
_INV_EXP_BITS = np.array(
    [((FR_MOD - 2) >> i) & 1 for i in range((FR_MOD - 2).bit_length())],
    dtype=np.int32,
)


_UNIT_FR = _int_to_limbs(1)
_UNIT_PLUS_R = _UNIT_FR + _R_LIMBS  # limbwise; normalized with the sum


def fr_mul(a, b):
    """Montgomery product a·b·R⁻¹ mod r over [..., 32] int32 limbs."""
    return _mont_mul(a, b, _R_LIMBS, _NPRIME_LIMBS, jnp.asarray(_RBAR_LIMBS))


def fr_add(a, b):
    return _mod_add(a, b, jnp.asarray(_RBAR_LIMBS))


def fr_sub(a, b):
    return _mod_sub(a, b, jnp.asarray(_UNIT_FR), jnp.asarray(_UNIT_PLUS_R))


def fr_inv(a):
    """Fermat inverse a^(r−2), vectorized over leading axes. a must be
    nonzero mod r (inverse of 0 returns 0 — harmless: callers mask)."""
    bits = jnp.asarray(_INV_EXP_BITS)
    one = jnp.broadcast_to(jnp.asarray(_ONE_MONT), a.shape).astype(jnp.int32)

    def body(i, acc):
        # LSB-first square-and-multiply: acc *= base when bit set
        base, out = acc
        out = jnp.where((bits[i] > 0)[..., None], fr_mul(out, base), out)
        base = fr_mul(base, base)
        return (base, out)

    _, out = lax.fori_loop(0, _INV_EXP_BITS.shape[0], body, (a, one))
    return out


def _tree_sum(v):
    """Log-depth Σ over axis -2 of [..., n, 32] (n a power of two)."""
    n = v.shape[-2]
    while n > 1:
        half = n // 2
        v = fr_add(v[..., :half, :], v[..., half : 2 * half, :])
        n = half
    return v[..., 0, :]


# ---------------------------------------------------------------------------
# Host <-> device
# ---------------------------------------------------------------------------


def fr_to_device(values) -> np.ndarray:
    """Iterable of ints mod r → [n, 32] Montgomery limb array."""
    return ints_to_limbs([v % FR_MOD * R_MONT_FR % FR_MOD for v in values], NLIMB_FR)


def fr_from_device(arr) -> list[int]:
    rinv = pow(R_MONT_FR, -1, FR_MOD)
    host = np.asarray(arr)
    return [
        _limbs_to_int(row) * rinv % FR_MOD
        for row in host.reshape(-1, NLIMB_FR)
    ]


# ---------------------------------------------------------------------------
# KZG kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("log_n",))
def barycentric_eval_batch(evals, roots, zs, log_n: int):
    """p_j(z_j) for a batch of evaluation-form polynomials.

    evals: [m, n, 32]  blob field elements (Montgomery)
    roots: [n, 32]     bit-reversed domain (shared across the batch)
    zs:    [m, 32]     evaluation points (must not hit a domain point —
                       the host pre-checks and short-circuits those)
    Returns ys: [m, 32] in Montgomery form.

    y = (z^n − 1)·n⁻¹ · Σᵢ pᵢ·wᵢ·(z − wᵢ)⁻¹
    """
    n = 1 << log_n
    m = evals.shape[0]
    z_b = jnp.broadcast_to(zs[:, None, :], (m, n, NLIMB_FR))
    roots_b = jnp.broadcast_to(roots[None, :, :], (m, n, NLIMB_FR))
    d = fr_sub(z_b, roots_b)
    dinv = fr_inv(d)
    terms = fr_mul(fr_mul(evals, roots_b), dinv)
    s = _tree_sum(terms)  # [m, 32]
    # z^n by log_n squarings
    zn = zs
    for _ in range(log_n):
        zn = fr_mul(zn, zn)
    one = jnp.broadcast_to(jnp.asarray(_ONE_MONT), zn.shape).astype(jnp.int32)
    num = fr_sub(zn, one)
    n_inv = jnp.asarray(
        fr_to_device([pow(n, FR_MOD - 2, FR_MOD)])[0]
    )
    n_inv = jnp.broadcast_to(n_inv, zn.shape)
    return fr_mul(fr_mul(s, num), n_inv)


@jax.jit
def quotient_batch(evals, roots, z, y):
    """Pointwise opening quotient qᵢ = (pᵢ − y)·(wᵢ − z)⁻¹ over the domain.

    evals/roots: [n, 32]; z/y: [32]. Lanes where wᵢ == z produce 0 (the
    host fills the special-case lane). Returns [n, 32] Montgomery.
    """
    n = evals.shape[0]
    z_b = jnp.broadcast_to(z[None, :], (n, NLIMB_FR))
    y_b = jnp.broadcast_to(y[None, :], (n, NLIMB_FR))
    d = fr_sub(roots, z_b)
    return fr_mul(fr_sub(evals, y_b), fr_inv(d))
