"""Fully-device BLS batch signature verification.

TPU analog of blst's `verify_multiple_aggregate_signatures`
(crypto/bls/src/impls/blst.rs:35-117) — the random-linear-combination batch
check

    e(-G1, Σ rᵢ·sigᵢ) · Π_m e(Σ_{i: msgᵢ=m} rᵢ·aggpkᵢ, H(m)) == 1

with every group operation and the Miller loops on device, as six jitted
programs a chunk, about one a stage, in launch order:

  1. H(m) hash-to-curve           — device SSWU, isogeny and cofactor
                                     clearing (bls381_htc; host does only
                                     the SHA-256 expand_message_xmd),
                                     launched first, so the device hashes
                                     while the host decodes the keys
  2. per-set pubkey aggregation   — a loop over the padded committee axis
                                     (G1, Fq lanes)
  3. rᵢ scalar multiplications    — batched double-and-add ladders (bls381),
     the signature sum Σ rᵢ·sigᵢ  — a G2 halving tree, and
     G2 subgroup checks on sigs   — ψ(Q) == [x]Q, [|x|]Q as extra lanes of
                                     the G2 ladder (bls381_pairing)
  4. per-message pubkey sums      — gather + the same committee loop
  5. Jacobian→affine              — one shared Fermat inversion, then
  6. Miller loops                   one lane per pair (bls381_pairing)

No device operation runs between them: the host only stages numpy
inputs, launches the programs, and reads the results at two points (the
subgroup verdicts, the Miller lanes). The host's remaining jobs: point
decompression (bytes → ints, cached on the PublicKey/Signature wrappers),
RLC scalar sampling, the product of the Miller lanes and the one final
exponentiation, and padding every batch to a few shapes (the set axis to
the device chunk, committees to a power-of-two bucket of AGG_LANES
slices), so one set of compiled programs serves every batch — the
reference batches gossip work in fixed chunks of 64 for the same reason,
beacon_processor/src/lib.rs:200.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls12_381 import FQ, FQ2, G1_GEN
from ..crypto.bls12_381.curve import pt_neg, to_affine
from ..crypto.bls12_381.fields import P
from ..metrics import REGISTRY
from ..utils.tracing import span
from .bls381 import (
    NLIMB,
    DevFq,
    DevFq2,
    batch_g1_scalar_mul,
    batch_g2_scalar_mul,
    fold_sum,
    fq_to_device,
    mont_mul,
    pt_add,
    scalars_to_bits,
)
from .bls381_htc import hash_to_g2_device, messages_to_field_device
from .bls381_pairing import (
    X_BITS,
    _miller_jit,
    _one_fq2,
    pad_pairing_lanes,
    pairing_host_tail,
    psi_is_neg,
)
from .bls381_tower import f2_mul, f2_norm, f2_scale_inv, f2_sqr, fq_inv

#: committee/group lanes summed per loop step: the committee axis is
#: walked in fixed-width slices, so one compiled program serves every
#: committee size in a bucket
AGG_LANES = 16


# ---------------------------------------------------------------------------
# Traced pieces of the programs
# ---------------------------------------------------------------------------


def _segment_sum(xs, ys, zs, slices):
    """[n, k] padded G1 points (infinity pads, k a multiple of AGG_LANES)
    → [n] sums over the first `slices` AGG_LANES-wide slices: a runtime
    count, so every committee size up to k shares one program."""
    acc = (xs[:, 0], ys[:, 0], jnp.zeros_like(zs[:, 0]))  # infinity

    def body(j, acc):
        sl = tuple(
            lax.dynamic_slice_in_dim(c, j * AGG_LANES, AGG_LANES, axis=1)
            for c in (xs, ys, zs)
        )
        return fold_sum(DevFq, acc, sl)

    return lax.fori_loop(0, slices, body, acc)


def _g2_sum(pt):
    """Sum a batch of G2 points ([n] → [1]) as a halving tree: a loop over
    the levels whose body is one [n]-lane G2 add, with the lanes past the
    live half set to infinity (all-zero coordinates)."""
    n = pt[0].shape[0]
    assert n & (n - 1) == 0, "power-of-two lane count"
    lane = jnp.arange(n)

    def level(i, pt):
        half = jnp.right_shift(n, i + 1)
        live = (lane < half)[:, None, None]
        lo = tuple(jnp.where(live, c, 0) for c in pt)
        hi = tuple(jnp.where(live, jnp.roll(c, -half, axis=0), 0) for c in pt)
        return pt_add(DevFq2, lo, hi)

    pt = lax.fori_loop(0, n.bit_length() - 1, level, pt)
    return tuple(c[:1] for c in pt)


def _g1_affine_from_inv(x, y, z, zinv):
    zinv2 = mont_mul(zinv, zinv)
    ax = mont_mul(x, zinv2)
    ay = mont_mul(y, mont_mul(zinv2, zinv))
    inf = jnp.all(z == 0, axis=-1)
    return ax, ay, inf


def _g2_affine_from_inv(x, y, z, zinv):
    zinv2 = f2_sqr(zinv)
    ax = f2_mul(x, zinv2)
    ay = f2_mul(y, f2_mul(zinv2, zinv))
    inf = jnp.all(z == 0, axis=(-1, -2))
    return ax, ay, inf


# ---------------------------------------------------------------------------
# The chunk's programs, one a stage (the hash is bls381_htc.hash_to_g2_device,
# the Miller loop bls381_pairing._miller_jit)
# ---------------------------------------------------------------------------


@jax.jit
def _aggregate(grid, slices):
    """Per-set pubkey sums over the padded committee axis."""
    return _segment_sum(*grid, slices)


@jax.jit
def _ladders(agg, sigs, bits):
    """The RLC ladders rᵢ·aggpkᵢ and rᵢ·sigᵢ, the signature sum Σ rᵢ·sigᵢ,
    and the signatures' subgroup check (bls381_pairing.psi_is_neg), whose
    [|x|]·sigᵢ rides the G2 ladder as a second half of its lanes
    (infinity signature lanes get z = 0)."""
    qx, qy, q_inf = sigs
    n = q_inf.shape[0]
    s_pk = batch_g1_scalar_mul(*agg, bits)
    one2 = _one_fq2((n,))
    z_pad = jnp.where(q_inf[:, None, None], jnp.zeros_like(one2), one2)
    twice = (jnp.concatenate([c, c]) for c in (qx, qy, z_pad))
    x_bits = jnp.broadcast_to(jnp.asarray(X_BITS), bits.shape)
    g2 = batch_g2_scalar_mul(*twice, jnp.concatenate([bits, x_bits]))
    in_sub = psi_is_neg(qx, qy, q_inf, *(c[n:] for c in g2))
    return s_pk, _g2_sum(tuple(c[:n] for c in g2)), in_sub


@jax.jit
def _message_groups(s_pk, idx, slices):
    """Per-message sums of the scaled pubkeys: one infinity lane appended
    (the gather's pad slot where no padded set lane serves), the gather by
    the host's index rows, then the committee loop over them."""
    grid = (
        jnp.take(jnp.concatenate([c, jnp.zeros_like(c[-1:])]), idx, axis=0)
        for c in s_pk
    )
    return _segment_sum(*grid, slices)


_NEG_G1 = to_affine(FQ, pt_neg(FQ, G1_GEN))
_NEG_G1_X, _NEG_G1_Y = fq_to_device([_NEG_G1[0]]), fq_to_device([_NEG_G1[1]])


@jax.jit
def _pairing_inputs(msg_pk, agg_sig, hm, m_count):
    """The multi-pairing's inputs (-G1, Σ rᵢ·sigᵢ) + (msg_pkₘ, H(m)) in
    affine form, padded to the Miller loop's lanes: every Jacobian → affine
    conversion shares ONE Fq inversion (the G1 z's and the norms of the G2
    z's ride one pow loop); message lanes past `m_count` are masked."""
    mb = msg_pk[0].shape[0]
    q = tuple(jnp.concatenate(c) for c in zip(agg_sig, hm))
    inv = fq_inv(jnp.concatenate([msg_pk[2], f2_norm(q[2])]))
    pk_ax, pk_ay, pk_inf = _g1_affine_from_inv(*msg_pk, inv[:mb])
    pk_inf = pk_inf | (jnp.arange(mb) >= m_count)
    qx, qy, q_inf = _g2_affine_from_inv(*q, f2_scale_inv(q[2], inv[mb:]))
    xp = jnp.concatenate([jnp.asarray(_NEG_G1_X), pk_ax])
    yp = jnp.concatenate([jnp.asarray(_NEG_G1_Y), pk_ay])
    p_inf = jnp.concatenate([jnp.zeros(1, bool), pk_inf])
    return pad_pairing_lanes(xp, yp, p_inf, qx, qy, q_inf)


# ---------------------------------------------------------------------------
# Host-side staging
# ---------------------------------------------------------------------------


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _slices(k: int) -> np.int32:
    """AGG_LANES slices that hold k lanes."""
    return np.int32(-(-k // AGG_LANES))


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
        return to_affine(FQ2, (x, y, z))
    if z == 0:
        return None
    if z == 1:
        return (x, y)
    return to_affine(FQ, (x, y, z))


def _g2_affine_limbs(points):
    """Host G2 affine pairs ((x0,x1),(y0,y1)) or None → numpy (x, y, inf):
    x/y [n, 2, 48] Montgomery limbs, infinity lanes all zero."""
    coords = [(0, 0, 0, 0) if a is None else (*a[0], *a[1]) for a in points]
    limbs = fq_to_device([c for row in coords for c in row]).reshape(-1, 2, 2, NLIMB)
    inf = np.array([a is None for a in points], dtype=bool)
    return limbs[:, 0], limbs[:, 1], inf


def _g1_affine_grid(grids):
    """[n][k] host affine-or-None G1 → Jacobian numpy limbs [n, k, 48]×3
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
    return tuple(a.reshape(n, k, NLIMB) for a in (xs, ys, zs))


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
_PROGRAMS = REGISTRY.counter(
    "bls_device_programs_total",
    "device programs the device verifier launched, a handful a chunk",
)
_PROGRAMS.inc(0)


class _Launches:
    """Launches one chunk's device programs and counts them."""

    def __init__(self):
        self.count = 0

    def __call__(self, program, *args):
        self.count += 1
        return program(*args)


def verify_signature_sets_device_full(sets, rng=None) -> bool:
    """Full-device RLC batch verification. Each set: (signature, pubkeys[],
    message). Returns True iff every signature is valid (w.h.p.).

    One `bls_device_verify` span (sets, lanes, keys, and at its end the
    device programs it launched) a call, with a child span for each stage
    in order; the two places the host waits on the device are
    `bls_device_wait` spans inside their stage, and the pairing stage ends
    in the host's `bls_pairing_host_tail`."""
    sets = list(sets)
    if not sets:
        return False
    from ..crypto import bls

    n = len(sets)
    # a few shapes for every chunk the node sends: the set axis pads to the
    # device chunk, messages pad to the set count, committees to a
    # power-of-two bucket of AGG_LANES slices
    nb = _bucket(n, floor=bls.DEFAULT_DEVICE_CHUNK)
    kb = _bucket(max(len(s.pubkeys) for s in sets), floor=AGG_LANES)
    _BATCHES.inc()
    _SETS.inc(n)
    _SET_LANES.inc(nb)
    launch = _Launches()
    with span("bls_device_verify", sets=n, lanes=nb, keys=kb) as call:
        try:
            return _verify_chunk(sets, rng, nb, kb, launch)
        finally:
            call.fields["programs"] = launch.count
            _PROGRAMS.inc(launch.count)


def _verify_chunk(sets, rng, nb: int, kb: int, launch) -> bool:
    import secrets as _secrets

    from ..crypto import bls

    n = len(sets)
    rand = rng if rng is not None else _secrets.SystemRandom()
    with span("bls_device_decode"):
        sig_affs = []
        scalars = []
        for s in sets:
            try:
                if s.signature.is_infinity():
                    return False
                sig_aff = _affine_int(s.signature.point())
            except (bls.BlsError, ValueError):
                return False
            if sig_aff is None or not s.pubkeys:
                return False
            r = 0
            while r == 0:
                r = rand.getrandbits(bls.RAND_BITS)
            sig_affs.append(sig_aff)
            scalars.append(r)

    # --- H(m): host expand_message_xmd, device hash-to-curve; launched
    # first, so the device hashes while the host decodes and stages the
    # keys ---
    with span("bls_device_hash_to_g2"):
        groups: dict[bytes, list[int]] = {}
        for i, s in enumerate(sets):
            groups.setdefault(s.message, []).append(i)
        msgs = list(groups)
        m_count = len(msgs)
        u = messages_to_field_device(msgs + [b"\x00" * 32] * (nb - m_count))
        hm = launch(hash_to_g2_device, u)

    # --- per-set pubkey aggregation (padded committee axis) ---
    with span("bls_device_aggregate"):
        try:
            pk_rows = [[_affine_int(pk.point()) for pk in s.pubkeys] for s in sets]
        except (bls.BlsError, ValueError):
            return False
        grid = [row + [None] * (kb - len(row)) for row in pk_rows]
        grid += [[None] * kb] * (nb - n)
        slices = _slices(max(len(row) for row in pk_rows))
        agg = launch(_aggregate, _g1_affine_grid(grid), slices)

    # --- RLC scalar multiplications, the signature aggregate and the
    # signatures' G2 subgroup checks ---
    with span("bls_device_ladders"):
        sigs = _g2_affine_limbs(sig_affs + [None] * (nb - n))
        bits = scalars_to_bits(scalars + [0] * (nb - n), bls.RAND_BITS)
        s_pk, agg_sig, in_sub = launch(_ladders, agg, sigs, bits)

    # --- per-message aggregation of scaled pubkeys (gather + sum) ---
    with span("bls_device_message_groups"):
        # the gather's pad slot: a padded set lane (scalar 0 ladder →
        # infinity) where there is one, else the appended infinity lane
        pad_slot = nb - 1 if nb > n else nb
        idx = np.full((nb, nb), pad_slot, dtype=np.int32)
        for gi, m in enumerate(msgs):
            idx[gi, : len(groups[m])] = groups[m]
        slices = _slices(max(len(v) for v in groups.values()))
        msg_pk = launch(_message_groups, s_pk, idx, slices)

    # --- the subgroup verdicts, read while the device sums the groups ---
    with span("bls_device_subgroup"):
        with span("bls_device_wait"):
            in_sub = np.asarray(in_sub)
        if not bool(in_sub.all()):
            return False

    # --- the multi-pairing: (-G1, agg_sig) + (msg_pk_i, H(m_i)) ---
    with span("bls_device_pairing"):
        xp, yp, qx, qy, skip = launch(_pairing_inputs, msg_pk, agg_sig, hm, np.int32(m_count))
        lanes = launch(_miller_jit, xp, yp, qx, qy)
        with span("bls_device_wait"):
            lanes, skip = jax.device_get((lanes, skip))
        return pairing_host_tail(lanes, skip)
