"""Device (batched, jittable) optimal-ate pairing for BLS12-381.

This is the TPU analog of blst's `verify_multiple_aggregate_signatures`
multi-pairing core (reference: crypto/bls/src/impls/blst.rs:112-117) — the
single most important kernel for north-star metric 1 (BASELINE.md): the
Miller loops of a signature batch run vmapped over the batch dimension on
the device; their product and ONE final exponentiation, which decide the
whole batch, run on the host (`pairing_host_tail`).

Design notes (derived, not transliterated — the reference's backend is
vendored C/assembly):

* The Miller loop runs on the TWIST: Q stays in E'(Fq2) Jacobian
  coordinates; no per-element untwisting into Fq12 (the slow host oracle in
  crypto/bls12_381/pairing_reference.py untwists — correct but scalar; the
  optimized host path in crypto/bls12_381/pairing.py now also stays on the
  twist). Line
  functions are derived by clearing denominators of the affine tangent /
  chord slope against untwisted coordinates (x·w⁻², y·w⁻³, tower w²=v,
  w⁶=ξ):

      tangent at T=(X,Y,Z):  a0 = −2YZ³·ξ·yp   b1 = 2Y²−3X³   b2 = 3X²Z²·xp
      chord  T→(x2,y2):      a0 = −Zλ·ξ·yp     b1 = Zλy2−θx2  b2 = θ·xp
                             (θ = y2Z³−Y, λ = x2Z²−X)

  giving the sparse Fq12 element l = (a0,0,0) + (0,b1,b2)·w. Scaling lines
  by Fq2 factors (the cleared denominators and one ξ) is sound: subfield
  elements die in the final exponentiation's (p⁶−1) easy part.

* The check ends on the host: after the Miller loop a check is one
  product of the lanes and one final exponentiation of a single Fq12
  element, a chain of dependent operations with no batch axis. On the
  device each Fq12 product in that chain costs its full latency (~0.5 ms,
  TPU v5e); the host oracle's exact `final_exponentiation`
  (crypto/bls12_381/pairing.py) runs the whole tail in milliseconds.

* G2 subgroup membership uses the ψ-endomorphism criterion
  ψ(Q) == [x]Q (valid since p ≡ x (mod r), verified in-repo; ψ = twist ∘
  Frobenius ∘ untwist has twisted coordinates ψ(x,y) = (ξ^(−(p−1)/3)·x̄,
  ξ^(−(p−1)/2)·ȳ)). A 64-iteration batched ladder replaces the 255-bit
  order multiplication the host oracle uses.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.bls12_381 import fields as HF
from ..crypto.bls12_381.fields import P, R, X
from ..crypto.bls12_381.pairing import final_exponentiation
from ..metrics import REGISTRY
from ..utils.tracing import span
from .bls381 import (
    NLIMB,
    _stack,
    DevFq2,
    _ONE_MONT,
    fq_to_device,
    pt_add,
    pt_scalar_mul,
    scalars_to_bits,
)
from .bls381_tower import (
    f2_add,
    f2_conj,
    f2_double,
    f2_is_zero,
    f2_mul,
    f2_mul_xi,
    f2_neg,
    f2_sub,
    f2_triple,
    f12_conj,
    f12_mul,
    f12_ones,
    f12_sqr,
    f12_from_device,
    fq2_const,
)

# ---------------------------------------------------------------------------
# Constants
# ---------------------------------------------------------------------------

_ATE = abs(X)  # 0xd201000000010000
# MSB-first bits after the leading 1 (63 entries) — the Miller loop schedule.
_ATE_TAIL_BITS = np.array([int(b) for b in bin(_ATE)[3:]], dtype=np.int32)

# ψ coefficients (host-derived): ξ^(−(p−1)/3), ξ^(−(p−1)/2)
_PSI_CX = fq2_const(HF.f2_pow(HF.f2_inv(HF.XI), (P - 1) // 3))
_PSI_CY = fq2_const(HF.f2_pow(HF.f2_inv(HF.XI), (P - 1) // 2))

assert P % R == X % R  # ψ acts as [x] on G2 — the subgroup criterion


# ---------------------------------------------------------------------------
# Point plumbing
# ---------------------------------------------------------------------------


def g1_affine_to_device(points):
    """Host G1 affine pairs (x, y) (None for infinity) → (xp, yp, inf_mask).
    xp/yp: [n, 48] Montgomery limbs; infinity lanes hold dummy (0,0)."""
    xs, ys, inf = [], [], []
    for aff in points:
        if aff is None:
            xs.append(0); ys.append(0); inf.append(True)
        else:
            xs.append(aff[0]); ys.append(aff[1]); inf.append(False)
    return (
        jnp.asarray(fq_to_device(xs)),
        jnp.asarray(fq_to_device(ys)),
        jnp.asarray(np.array(inf, dtype=bool)),
    )


def g2_affine_to_device(points):
    """Host G2 affine pairs ((x0,x1),(y0,y1)) or None → (x, y, inf_mask).
    x/y: [n, 2, 48]."""
    xs, ys, inf = [], [], []
    for aff in points:
        if aff is None:
            xs.append((0, 0)); ys.append((0, 0)); inf.append(True)
        else:
            xs.append(aff[0]); ys.append(aff[1]); inf.append(False)
    pack = lambda vals: jnp.asarray(np.stack([fq2_const(v) for v in vals]))
    return pack(xs), pack(ys), jnp.asarray(np.array(inf, dtype=bool))


def _one_fq(batch_shape):
    return jnp.broadcast_to(jnp.asarray(_ONE_MONT), (*batch_shape, NLIMB)).astype(jnp.int32)


def _one_fq2(batch_shape):
    one = _one_fq(batch_shape)
    return jnp.stack([one, jnp.zeros_like(one)], axis=-2)


# ---------------------------------------------------------------------------
# Miller loop steps
# ---------------------------------------------------------------------------


def _line_to_f12(a0, b1, b2):
    """Sparse line slots → dense Fq12 [..., 2, 3, 2, 48]."""
    z = jnp.zeros_like(a0)
    lo = jnp.stack([a0, z, z], axis=-3)
    hi = jnp.stack([z, b1, b2], axis=-3)
    return jnp.stack([lo, hi], axis=-4)


def _fq_as_f2(s):
    return jnp.stack([s, jnp.zeros_like(s)], axis=-2)


def _dbl_step(T, xp, yp):
    """Tangent line at T evaluated at P, then T ← 2T (dbl-2009-l). The
    line and the doubling share their squares; independent products ride
    one stacked Fq2 multiplication: three in all."""
    X, Y, Z = T
    XX, YY, ZZ, YZ = f2_mul(_stack(X, Y, Z, Y), _stack(X, Y, Z, Z))
    e = f2_triple(XX)
    xyy = f2_add(X, YY)
    YZ3, XXX, XXZZ, c, t, f = f2_mul(
        _stack(YZ, X, XX, YY, xyy, e), _stack(ZZ, XX, ZZ, YY, xyy, e)
    )
    d = f2_double(f2_sub(f2_sub(t, XX), c))
    x3 = f2_sub(f, f2_double(d))
    a0, b2, m = f2_mul(
        _stack(YZ3, f2_triple(XXZZ), e),
        _stack(_fq_as_f2(yp), _fq_as_f2(xp), f2_sub(d, x3)),
    )
    a0 = f2_mul_xi(f2_neg(f2_double(a0)))  # −2·Y·Z³·ξ·yp
    b1 = f2_sub(f2_double(YY), f2_triple(XXX))  # 2Y² − 3X³
    y3 = f2_sub(m, f2_double(f2_double(f2_double(c))))
    return _line_to_f12(a0, b1, b2), (x3, y3, f2_double(YZ))


def _add_step(T, q_x, q_y, xp, yp):
    """Chord line through T and the affine base Q evaluated at P, then
    T ← T + Q as a mixed addition (Z_Q = 1) whose h and r are the line's
    λ and θ: five stacked Fq2 multiplications. No lane needs the
    doubling/inverse special cases of a general add: T = [k]Q with
    1 < k < |x| < r, and the caller masks infinity lanes."""
    X, Y, Z = T
    ZZ, qyZ = f2_mul(_stack(Z, q_y), _stack(Z, Z))
    u2, s2 = f2_mul(_stack(q_x, qyZ), _stack(ZZ, ZZ))
    lam, theta = f2_sub(_stack(u2, s2), _stack(X, Y))  # h, r
    h2, r2 = f2_double(_stack(lam, theta))
    zlam, i, r2sq, tqx, b2 = f2_mul(
        _stack(Z, h2, r2, theta, theta), _stack(lam, h2, r2, q_x, _fq_as_f2(xp))
    )
    j, v, zqy, zyp = f2_mul(_stack(lam, X, zlam, zlam), _stack(i, i, q_y, _fq_as_f2(yp)))
    x3 = f2_sub(f2_sub(r2sq, j), f2_double(v))
    m, yj = f2_mul(_stack(r2, Y), _stack(f2_sub(v, x3), j))
    y3 = f2_sub(m, f2_double(yj))
    a0 = f2_mul_xi(f2_neg(zyp))  # −Z·λ·ξ·yp
    b1 = f2_sub(zqy, tqx)  # Z·λ·y2 − θ·x2
    return _line_to_f12(a0, b1, b2), (x3, y3, f2_double(zlam))


# double-and-add op schedule (host-precomputed): one scan step per group
# op instead of a fused dbl+add+select body. The schedule halves the scan
# body (each step is ONE of the two branches, compiled as separate HLO
# computations under lax.cond) and skips the wasted always-computed add of
# the branchless form — fewer flops AND tractable LLVM compiles.
_MILLER_OPS = []
for _b in _ATE_TAIL_BITS:
    _MILLER_OPS.append(0)  # double step
    if _b:
        _MILLER_OPS.append(1)  # add step
_MILLER_OPS = np.array(_MILLER_OPS, dtype=np.int32)


def miller_loop_batch(xp, yp, q_x, q_y):
    """Batched f_{|x|,Q}(P), conjugated for x<0. Inputs: G1 affine limbs
    [n, 48]×2, G2 (twisted) affine limbs [n, 2, 48]×2. Returns [n] Fq12.
    Infinity handling is the CALLER's job (skip those lanes)."""
    batch = xp.shape[:-1]
    one2 = _one_fq2(batch)
    T0 = (q_x, q_y, one2)
    f0 = f12_ones(batch)
    ops = jnp.asarray(_MILLER_OPS)

    def dbl_branch(carry):
        T, f = carry
        line, T2 = _dbl_step(T, xp, yp)
        return (T2, f12_mul(f12_sqr(f), line))

    def add_branch(carry):
        T, f = carry
        line, T2 = _add_step(T, q_x, q_y, xp, yp)
        return (T2, f12_mul(f, line))

    def body(carry, op):
        return lax.cond(op > 0, add_branch, dbl_branch, carry), None

    (_, f), _ = lax.scan(body, (T0, f0), ops)
    return f12_conj(f)  # x < 0


_miller_jit = jax.jit(miller_loop_batch)

#: Miller-loop lanes per call: one BLS chunk's messages plus the signature
#: pair (crypto.bls.DEFAULT_DEVICE_CHUNK + 1). Smaller batches (the KZG
#: pair checks) pad up with infinity lanes, so every caller shares one
#: compiled Miller loop.
PAIRING_LANES = 33

# registered at import, so the series reads zero before the first check
_HOST_TAILS = REGISTRY.counter(
    "bls_pairing_host_tails_total",
    "multi-pairing checks finished on the host: lane product and final exponentiation",
)
_HOST_TAILS.inc(0)


def pad_pairing_lanes(xp, yp, p_inf, q_x, q_y, q_inf):
    """The batch padded with infinity lanes to a multiple of
    PAIRING_LANES: (xp, yp, q_x, q_y, skip), skip marking the lanes with
    either point at infinity. Traced inside the verifier's pairing-input
    program; run eagerly by `miller_lanes_device`."""
    n = xp.shape[0]
    pad = -(-n // PAIRING_LANES) * PAIRING_LANES - n
    if pad:
        grow = lambda a, v: jnp.concatenate(  # noqa: E731
            [a, jnp.full((pad, *a.shape[1:]), v, a.dtype)]
        )
        xp, yp, q_x, q_y = (grow(a, 0) for a in (xp, yp, q_x, q_y))
        p_inf, q_inf = grow(p_inf, True), grow(q_inf, True)
    return xp, yp, q_x, q_y, p_inf | q_inf


def miller_lanes_device(xp, yp, p_inf, q_x, q_y, q_inf):
    """The device half of a multi-pairing check: the batch padded with
    infinity lanes to a multiple of PAIRING_LANES, one Miller loop per
    lane. Returns (lanes [n, 2, 3, 2, 48], skip [n]) as device arrays;
    a skipped lane (either point at infinity) holds junk and contributes
    the identity."""
    xp, yp, q_x, q_y, skip = pad_pairing_lanes(xp, yp, p_inf, q_x, q_y, q_inf)
    return _miller_jit(xp, yp, q_x, q_y), skip


def pairing_host_tail(lanes, skip) -> bool:
    """The host half: the product of the Miller lanes that are not skipped,
    then the host oracle's exact final exponentiation, compared with one."""
    with span("bls_pairing_host_tail", lanes=len(skip)):
        _HOST_TAILS.inc()
        f = HF.F12_ONE
        for lane, skipped in zip(f12_from_device(lanes), np.asarray(skip)):
            if not skipped:
                f = HF.f12_mul(f, lane)
        return HF.f12_is_one(final_exponentiation(f))


def multi_pairing_check_device(xp, yp, p_inf, q_x, q_y, q_inf) -> bool:
    """∏ e(P_i, Q_i) == 1 over the batch: Miller loops on the device, their
    product and the final exponentiation on the host. Infinity lanes
    contribute the identity (host oracle behavior)."""
    lanes, skip = jax.device_get(miller_lanes_device(xp, yp, p_inf, q_x, q_y, q_inf))
    return pairing_host_tail(lanes, skip)


# ---------------------------------------------------------------------------
# ψ endomorphism + G2 subgroup check
# ---------------------------------------------------------------------------


def psi(q_x, q_y):
    """ψ on twisted affine coordinates: (cx·x̄, cy·ȳ)."""
    return (
        f2_mul(f2_conj(q_x), jnp.asarray(_PSI_CX)),
        f2_mul(f2_conj(q_y), jnp.asarray(_PSI_CY)),
    )


def _psi_jac(T):
    """ψ on Jacobian coords: (cx·X̄, cy·Ȳ, Z̄) — ψ is Fq2-conjugate-linear
    and the coordinate weights stay consistent since Z̄ carries through."""
    Xc, Yc, Zc = T
    return (
        f2_mul(f2_conj(Xc), jnp.asarray(_PSI_CX)),
        f2_mul(f2_conj(Yc), jnp.asarray(_PSI_CY)),
        f2_conj(Zc),
    )


#: |x| as the RLC ladders take their scalars: 64 bits, least significant
#: first (`bls381.batch_g2_scalar_mul`)
X_BITS = scalars_to_bits([_ATE], 64)[0]


def psi_is_neg(q_x, q_y, q_inf, X, Y, Z):
    """The G2 subgroup check's compare, given (X:Y:Z) = [|x|]Q: Q is in G2
    iff ψ(Q) == [x]Q, and x < 0, so the test is ψ(Q) == −(X:Y:Z) in
    Jacobian coordinates (ψ(Q) is affine): x·Z² == X and y·Z³ == −Y with
    Z ≠ 0; infinity lanes pass. A 64-bit ladder instead of the host's
    255-bit order multiplication: the device verifier runs it as extra
    lanes of its RLC G2 ladder (bls381_verify._ladders)."""
    px, py = psi(q_x, q_y)
    ZZ, pyZ = f2_mul(_stack(Z, py), _stack(Z, Z))
    lhs_x, lhs_y = f2_mul(_stack(px, pyZ), _stack(ZZ, ZZ))
    same = (
        jnp.all(lhs_x == X, axis=(-1, -2))
        & jnp.all(f2_add(lhs_y, Y) == 0, axis=(-1, -2))
        & ~f2_is_zero(Z)
    )
    return same | q_inf


# ---------------------------------------------------------------------------
# Fast cofactor clearing (Budroni–Pintore form, identity verified in-repo):
#   [h_eff]Q = [x²−x−1]Q + [x−1]ψ(Q) + ψ²(2Q)
# ---------------------------------------------------------------------------


def _neg_y(T):
    x, y, z = T
    return (x, f2_neg(y), z)


@jax.jit
def g2_clear_cofactor_device(T):
    """Jacobian twisted point(s) → subgroup point(s); 2 x-ladders + 3 ψ
    instead of a 636-bit scalar multiplication. Both ladders are the two
    steps of one scan, Y ← [x]Y − T = −(T + [|x|]Y), each the RLC scalars'
    ladder over X_BITS started from T: [x−1]T, then [x²−x−1]T; a second
    scan adds ψ([x−1]T) and ψ²(T) twice (ψ²(2T)). A scan's body is
    compiled once, so the program holds one ladder and one G2 add past
    the ladder's own."""
    bits = jnp.broadcast_to(jnp.asarray(X_BITS), (*T[0].shape[:-2], X_BITS.shape[0]))

    def ladder_step(Y, _):
        Y = _neg_y(pt_scalar_mul(DevFq2, Y, bits, start=T))
        return Y, Y

    c3, ys = lax.scan(ladder_step, T, None, length=2)
    psi2 = _psi_jac(_psi_jac(T))
    terms = (_psi_jac(tuple(c[0] for c in ys)), psi2, psi2)
    terms = tuple(jnp.stack(c) for c in zip(*terms))
    out, _ = lax.scan(lambda acc, q: (pt_add(DevFq2, acc, q), None), c3, terms)
    return out
