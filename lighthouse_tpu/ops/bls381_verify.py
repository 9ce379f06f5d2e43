"""Fully-device BLS batch signature verification.

TPU analog of blst's `verify_multiple_aggregate_signatures`
(crypto/bls/src/impls/blst.rs:35-117) — the random-linear-combination batch
check

    e(-G1, Σ rᵢ·sigᵢ) · Π_m e(Σ_{i: msgᵢ=m} rᵢ·aggpkᵢ, H(m)) == 1

with every group operation and the Miller loops on device:

  1. per-set pubkey aggregation   — padded tree-reduction over the
                                     committee axis (G1, Fq lanes)
  2. G2 subgroup checks on sigs   — ψ-endomorphism ladder (bls381_pairing)
  3. rᵢ scalar multiplications    — batched double-and-add ladders (bls381)
  4. signature sum Σ rᵢ·sigᵢ     — G2 tree-reduction
  5. H(m) hash-to-curve           — device SSWU (bls381_htc; host does only
                                     the SHA-256 expand_message_xmd)
  6. Jacobian→affine              — batched Fermat inversions
  7. Miller loops                 — one lane per pair (bls381_pairing)

The host's remaining jobs: point decompression (bytes → ints, cached on the
PublicKey/Signature wrappers), RLC scalar sampling, the product of the
Miller lanes and the one final exponentiation, and padding every
batch to one shape (the set axis to the device chunk, committees to whole
AGG_LANES slices), so one set of compiled kernels serves every batch —
the reference batches gossip work in fixed chunks of 64 for the same
reason, beacon_processor/src/lib.rs:200.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.bls12_381.fields import P
from ..metrics import REGISTRY
from ..utils.tracing import span
from .bls381 import (
    NLIMB,
    DevFq,
    fq_to_device,
    g1_points_to_device,
    batch_g1_scalar_mul,
    batch_g2_scalar_mul,
    fold_sum,
    mont_mul,
    scalars_to_bits,
)
from .bls381_htc import (
    _jit_f2_norm,
    _jit_f2_scale_inv,
    fq_inv_staged,
    hash_to_g2_device,
    messages_to_field_device,
)
from .bls381_pairing import (
    _jit_pt_add_g2,
    g1_affine_to_device,
    g2_affine_to_device,
    g2_subgroup_check_device,
    miller_lanes_device,
    pairing_host_tail,
)

# ---------------------------------------------------------------------------
# Generic reductions / conversions
# ---------------------------------------------------------------------------


#: committee/group lanes summed per aggregation call: every committee size
#: runs through ONE compiled [n, AGG_LANES] kernel, looped over the
#: committee axis in fixed-width slices
AGG_LANES = 16


@jax.jit
def _jit_agg_step(ax, ay, az, xs, ys, zs):
    """acc [n] + Σ_k pts [n, AGG_LANES] (infinity pads) → [n]."""
    return fold_sum(DevFq, (ax, ay, az), (xs, ys, zs))


def g1_segment_sum(xs, ys, zs):
    """[n, k] padded G1 points (infinity pads, k a multiple of AGG_LANES)
    → [n] sums, in k / AGG_LANES calls of one compiled kernel."""
    n, k = xs.shape[:2]
    assert k % AGG_LANES == 0, "pad the committee axis to AGG_LANES"
    acc = (xs[:, 0], ys[:, 0], jnp.zeros_like(zs[:, 0]))  # infinity
    for j in range(0, k, AGG_LANES):
        sl = slice(j, j + AGG_LANES)
        acc = _jit_agg_step(*acc, xs[:, sl], ys[:, sl], zs[:, sl])
    return acc


def g2_sum_reduce(xs, ys, zs):
    """Sum a batch of G2 points ([n] → [1]) as a halving tree; every level
    is one call of the shared [n]-lane G2 add kernel, with the lanes past
    the live half set to infinity (z = 0)."""
    n = xs.shape[0]
    assert n & (n - 1) == 0, "power-of-two lane count"
    pt = (xs, ys, zs)
    while n > 1:
        n //= 2
        lo = tuple(c.at[n:].set(0) for c in pt)
        hi = tuple(jnp.roll(c, -n, axis=0).at[n:].set(0) for c in pt)
        pt = _jit_pt_add_g2(*lo, *hi)
    return tuple(c[:1] for c in pt)


@jax.jit
def _jit_g1_affine_from_inv(x, y, z, zinv):
    zinv2 = mont_mul(zinv, zinv)
    ax = mont_mul(x, zinv2)
    ay = mont_mul(y, mont_mul(zinv2, zinv))
    inf = jnp.all(z == 0, axis=-1)
    return ax, ay, inf


@jax.jit
def _jit_g2_affine_from_inv(x, y, z, zinv):
    from .bls381_tower import f2_mul, f2_sqr

    zinv2 = f2_sqr(zinv)
    ax = f2_mul(x, zinv2)
    ay = f2_mul(y, f2_mul(zinv2, zinv))
    inf = jnp.all(z == 0, axis=(-1, -2))
    return ax, ay, inf


# ---------------------------------------------------------------------------
# Host-side staging
# ---------------------------------------------------------------------------


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _affine_int(pt):
    """Host Jacobian int point → affine (x, y) or None; z==1 fast path (all
    decompressed points arrive affine)."""
    if pt is None:
        return None
    x, y, z = pt
    if isinstance(z, tuple):  # Fq2
        if z == (0, 0):
            return None
        if z == (1, 0):
            return (x, y)
        from ..crypto.bls12_381 import FQ2, to_affine

        return to_affine(FQ2, (x, y, z))
    if z == 0:
        return None
    if z == 1:
        return (x, y)
    from ..crypto.bls12_381 import FQ, to_affine

    return to_affine(FQ, (x, y, z))


_G1_INF_LIMBS = np.zeros(NLIMB, dtype=np.int32)


def _g1_affine_grid_to_device(grids):
    """[n][k] host affine-or-None G1 → Jacobian device arrays [n, k, 48]×3
    (infinity encoded z=0)."""
    from .bls381 import R_MONT, int_to_limbs, ints_to_limbs

    n = len(grids)
    k = len(grids[0])
    flat = [aff for row in grids for aff in row]
    live = [i for i, aff in enumerate(flat) if aff is not None]
    xs = np.zeros((n * k, NLIMB), dtype=np.int32)
    ys = np.zeros((n * k, NLIMB), dtype=np.int32)
    zs = np.zeros((n * k, NLIMB), dtype=np.int32)
    if live:
        xs[live] = ints_to_limbs([flat[i][0] * R_MONT % P for i in live])
        ys[live] = ints_to_limbs([flat[i][1] * R_MONT % P for i in live])
        zs[live] = int_to_limbs(R_MONT % P)
    return tuple(jnp.asarray(a.reshape(n, k, NLIMB)) for a in (xs, ys, zs))


# ---------------------------------------------------------------------------
# The batch verifier
# ---------------------------------------------------------------------------


# registered at import, so the series read zero before the first batch
_BATCHES = REGISTRY.counter(
    "bls_device_batches_total", "device batch verifications (one per chunk)"
)
_BATCHES.inc(0)
_SETS = REGISTRY.counter(
    "bls_device_sets_total", "signature sets sent to the device verifier"
)
_SETS.inc(0)
_SET_LANES = REGISTRY.counter(
    "bls_device_set_lanes_total",
    "set lanes the device verifier ran: the set axis padded to its chunk",
)
_SET_LANES.inc(0)


def verify_signature_sets_device_full(sets, rng=None) -> bool:
    """Full-device RLC batch verification. Each set: (signature, pubkeys[],
    message). Returns True iff every signature is valid (w.h.p.).

    One `bls_device_verify` span (sets, lanes, keys) a call, with a child
    span for each stage in order; the two places the host waits on the
    device are `bls_device_wait` spans inside their stage, and the pairing
    stage ends in the host's `bls_pairing_host_tail`."""
    sets = list(sets)
    if not sets:
        return False
    from ..crypto import bls

    n = len(sets)
    # one shape for every chunk the node sends: the set axis pads to the
    # device chunk, messages pad to the set count, committees and message
    # groups to whole AGG_LANES slices
    nb = _bucket(n, floor=bls.DEFAULT_DEVICE_CHUNK)
    kmax = _round_up(max(len(s.pubkeys) for s in sets), AGG_LANES)
    _BATCHES.inc()
    _SETS.inc(n)
    _SET_LANES.inc(nb)
    with span("bls_device_verify", sets=n, lanes=nb, keys=kmax):
        return _verify_chunk(sets, rng, nb, kmax)


def _verify_chunk(sets, rng, nb: int, kmax: int) -> bool:
    import secrets as _secrets

    from ..crypto import bls

    n = len(sets)
    rand = rng if rng is not None else _secrets.SystemRandom()
    with span("bls_device_decode"):
        sig_affs = []
        pk_rows = []
        scalars = []
        messages = []
        for s in sets:
            try:
                if s.signature.is_infinity():
                    return False
                sig_aff = _affine_int(s.signature.point())
                pk_affs = [_affine_int(pk.point()) for pk in s.pubkeys]
            except (bls.BlsError, ValueError):
                return False
            if sig_aff is None or not pk_affs:
                return False
            r = 0
            while r == 0:
                r = rand.getrandbits(bls.RAND_BITS)
            sig_affs.append(sig_aff)
            pk_rows.append(pk_affs)
            scalars.append(r)
            messages.append(s.message)

    # --- G2 subgroup checks on all signatures (device) ---
    with span("bls_device_subgroup"):
        sig_pad = sig_affs + [None] * (nb - n)
        qx, qy, q_inf = g2_affine_to_device(sig_pad)
        in_sub = g2_subgroup_check_device(qx, qy, q_inf)
        with span("bls_device_wait"):
            in_sub = np.asarray(in_sub)
        if not bool(in_sub.all()):
            return False

    # --- per-set pubkey aggregation (device, padded committee axis) ---
    with span("bls_device_aggregate"):
        grid = [row + [None] * (kmax - len(row)) for row in pk_rows]
        grid += [[None] * kmax] * (nb - n)
        gx, gy, gz = _g1_affine_grid_to_device(grid)
        agg_x, agg_y, agg_z = g1_segment_sum(gx, gy, gz)

    # --- RLC scalar multiplications (device ladders) and the signature
    # aggregate Σ rᵢ·sigᵢ (device tree-reduce) ---
    with span("bls_device_ladders"):
        bits = jnp.asarray(scalars_to_bits(scalars + [0] * (nb - n), bls.RAND_BITS))
        s_pk = batch_g1_scalar_mul(agg_x, agg_y, agg_z, bits)
        one2 = jnp.broadcast_to(
            jnp.stack(
                [jnp.asarray(fq_to_device([1])[0]), jnp.zeros(NLIMB, jnp.int32)]
            ),
            (nb, 2, NLIMB),
        ).astype(jnp.int32)
        z_pad = jnp.where(q_inf[:, None, None], jnp.zeros_like(one2), one2)
        s_sig = batch_g2_scalar_mul(qx, qy, z_pad, bits)
        agg_sig = g2_sum_reduce(*s_sig)

    # --- per-message aggregation of scaled pubkeys (device gather+reduce) ---
    with span("bls_device_message_groups"):
        groups: dict[bytes, list[int]] = {}
        for i, m in enumerate(messages):
            groups.setdefault(m, []).append(i)
        msgs = list(groups)
        m_count = len(msgs)
        mb = nb
        gmax = _round_up(max(len(v) for v in groups.values()), AGG_LANES)
        if nb > n:
            # lane nb-1 is a padded set (scalar 0 ladder → infinity): reuse it
            # as the gather pad slot.
            pad_slot = nb - 1
        else:
            # exact-power batch: append an explicit infinity lane.
            s_pk = tuple(
                jnp.concatenate([c, jnp.zeros_like(c[-1:])], axis=0) for c in s_pk
            )
            pad_slot = nb
        idx = np.full((mb, gmax), pad_slot, dtype=np.int32)
        for gi, m in enumerate(msgs):
            for jj, si in enumerate(groups[m]):
                idx[gi, jj] = si
        gx2 = tuple(jnp.take(c, jnp.asarray(idx), axis=0) for c in s_pk)
        msg_pk = g1_segment_sum(*gx2)

    # --- H(m): host expand_message_xmd, device SSWU hash-to-curve ---
    with span("bls_device_hash_to_g2"):
        u = messages_to_field_device(msgs + [b"\x00" * 32] * (mb - m_count))
        hm = hash_to_g2_device(jnp.asarray(u))

    # --- assemble the multi-pairing: (-G1, agg_sig) + (msg_pk_i, H(m_i)) ---
    with span("bls_device_pairing"):
        from ..crypto.bls12_381 import FQ, G1_GEN
        from ..crypto.bls12_381.curve import pt_neg, to_affine

        neg_g1 = to_affine(FQ, pt_neg(FQ, G1_GEN))
        ngx, ngy, ng_inf = g1_affine_to_device([neg_g1])

        # Jacobian → affine for every pairing input with ONE shared Fq
        # inversion: the G1 z's and the norms of the G2 z's ride one pow call
        q = tuple(jnp.concatenate([a, h], axis=0) for a, h in zip(agg_sig, hm))
        inv = fq_inv_staged(jnp.concatenate([msg_pk[2], _jit_f2_norm(q[2])]))
        pk_ax, pk_ay, pk_inf = _jit_g1_affine_from_inv(*msg_pk, inv[:mb])
        # mask out padded message lanes
        pk_inf = pk_inf | jnp.asarray(np.arange(mb) >= m_count)
        qx2, qy2, q_inf2 = _jit_g2_affine_from_inv(
            *q, _jit_f2_scale_inv(q[2], inv[mb:])
        )

        xp = jnp.concatenate([ngx, pk_ax], axis=0)
        yp = jnp.concatenate([ngy, pk_ay], axis=0)
        p_inf = jnp.concatenate([ng_inf, pk_inf], axis=0)
        lanes, skip = miller_lanes_device(xp, yp, p_inf, qx2, qy2, q_inf2)
        with span("bls_device_wait"):
            lanes, skip = jax.device_get((lanes, skip))
        return pairing_host_tail(lanes, skip)
