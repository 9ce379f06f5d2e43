"""Device Pippenger multi-scalar multiplication over G1.

The commitment-scale MSM (Σ sᵢ·Pᵢ, 4096 Lagrange setup points per blob —
crypto/kzg/src/lib.rs:110 `blob_to_kzg_commitment`, SURVEY §2.7-2/§7
step 2), bucketized like blst's Pippenger and laid out so that every
compiled program holds only a few point additions:

  * the HOST splits each scalar into 4-bit window digits and writes, for
    every (window, nonzero digit) bucket, the indices of its points into a
    fixed-shape table [windows × 15 buckets, slots] padded with an
    infinity index (numpy — the scalars live on the host anyway). A
    bucket fuller than `slots` (skewed scalars) spills into further
    rounds of the same table shape;
  * the DEVICE gathers the table's points and folds each bucket's slots
    with one add per step (960 bucket lanes), then forms Σ_b b·B_b per
    window by the running-sum trick and combines the windows by Horner
    (four doublings per window).

Shapes depend only on the number of points, so one set of compiled
programs serves every call on a setup. (An associative-scan formulation
of the bucket sums unrolls dozens of point additions into one program and
does not compile in bounded time for the TPU.)

Points are Jacobian [n, 48] Montgomery limb arrays (ops/bls381 layout);
infinity is Z == 0, so masking is free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .bls381 import NLIMB, DevFq, fold_sum, g1_points_from_device, pt_add, pt_double

WINDOW = 4  # digit bits; 64 windows cover 255-bit Fr scalars
NBITS = 256  # scalars are reduced mod r < 2^255; one spare window bit
NWIN = NBITS // WINDOW
NBUCKETS = (1 << WINDOW) - 1  # nonzero digits


def _slots(n: int) -> int:
    """Table width: ~1.5× the mean bucket load, a power of two ≥ 8."""
    want = max(8, -(-3 * n // (2 * NBUCKETS)))
    return 1 << (want - 1).bit_length()


def bucket_tables(scalars, n: int) -> np.ndarray:
    """[rounds, NWIN·NBUCKETS, slots] int32 point indices; index n is the
    infinity pad. Row w·NBUCKETS + (d−1) lists the points whose window-w
    digit is d."""
    raw = b"".join(int(s).to_bytes(NBITS // 8, "little") for s in scalars)
    by = np.frombuffer(raw, dtype=np.uint8).reshape(n, NBITS // 8)
    digits = np.stack([by & 0xF, by >> 4], axis=-1).reshape(n, NWIN).T  # [w, n]
    slots = _slots(n)
    order = np.argsort(digits, axis=1, kind="stable")
    sd = np.take_along_axis(digits, order, axis=1).astype(np.int64)
    # rank of each sorted entry inside its (window, digit) bucket
    first = np.stack(
        [np.searchsorted(row, row, side="left") for row in sd]
    )  # first index of this entry's digit value in the sorted row
    rank = np.arange(n)[None, :] - first
    live = sd > 0
    rounds = max(1, int(-(-(rank[live].max(initial=-1) + 1) // slots)))
    table = np.full((rounds, NWIN * NBUCKETS, slots), n, dtype=np.int32)
    w_idx = np.broadcast_to(np.arange(NWIN)[:, None], sd.shape)
    r = rank[live]
    table[r // slots, w_idx[live] * NBUCKETS + sd[live] - 1, r % slots] = order[live]
    return table


def _infinity(shape):
    from .bls381 import _ONE_MONT

    one = jnp.broadcast_to(jnp.asarray(_ONE_MONT), (*shape, NLIMB)).astype(jnp.int32)
    return (one, one, jnp.zeros((*shape, NLIMB), dtype=jnp.int32))


@jax.jit
def _bucket_round(ax, ay, az, xs, ys, zs, table):
    """acc [L] + the points table [L, S] names (index n = infinity)."""
    pts = tuple(
        jnp.concatenate([c, jnp.zeros_like(c[:1])], axis=0) for c in (xs, ys, zs)
    )
    return fold_sum(DevFq, (ax, ay, az), tuple(jnp.take(c, table, axis=0) for c in pts))


@jax.jit
def _window_combine(bx, by, bz):
    """Bucket sums [NWIN, NBUCKETS] (digit d at column d−1) → Σ sᵢ·Pᵢ [1]."""
    nwin, nb = bx.shape[:2]

    def running_sum(k, carry):
        running, total = carry
        col = tuple(
            lax.dynamic_index_in_dim(c, nb - 1 - k, axis=1, keepdims=False)
            for c in (bx, by, bz)
        )
        running = pt_add(DevFq, running, col)
        return running, pt_add(DevFq, total, running)

    inf = _infinity((nwin,))
    _, wsum = lax.fori_loop(0, nb, running_sum, (inf, inf))  # Σ_d d·B_d

    def horner(i, acc):
        acc = lax.fori_loop(0, WINDOW, lambda _, a: pt_double(DevFq, a), acc)
        w = tuple(lax.dynamic_slice_in_dim(c, nwin - 1 - i, 1) for c in wsum)
        return pt_add(DevFq, acc, w)

    return lax.fori_loop(0, nwin, horner, _infinity((1,)))


def g1_msm_device(scalars, points_dev):
    """Host entry: scalars (list[int] mod r) × device points → host
    Jacobian int tuple. `points_dev` = (xs, ys, zs) [n, 48] arrays (keep
    the setup resident on device across calls — see TrustedSetup)."""
    n = points_dev[0].shape[0]
    acc = _infinity((NWIN * NBUCKETS,))
    for table in bucket_tables(scalars, n):
        acc = _bucket_round(*acc, *points_dev, jnp.asarray(table))
    b = tuple(c.reshape(NWIN, NBUCKETS, NLIMB) for c in acc)
    return g1_points_from_device(_window_combine(*b))[0]
