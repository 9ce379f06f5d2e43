"""Multi-chip sharded BLS batch step: RLC scalar-muls + ICI point-sum.

The distributed half of batch signature verification (SURVEY.md §2.9): the
signature-set batch is sharded over the `batch` mesh axis — each device
runs the 64-bit RLC scalar-multiplication ladders for its shard of G1
points (the per-set aggregated pubkeys) and tree-reduces its shard to one
partial sum; the per-device partial sums ride ICI via `all_gather`, and the
tiny [n_devices] tail is folded replicated on every device. This mirrors
the reference's rayon chunk map-reduce over signature sets
(consensus/state_processing/src/per_block_processing/
block_signature_verifier.rs:396-404) with the chunk axis mapped onto the
device mesh instead of CPU threads.

Point addition is not an arithmetic `psum`, so the reduction is an
`all_gather` + replicated Jacobian fold (n_devices adds) — negligible
next to the 64-iteration ladders and bandwidth-wise just 3·48 int32 limbs
per device. Both sums are fold loops of single adds, so the step holds
three point operations whatever the shard size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .bls381 import DevFq, fold_sum, pt_scalar_mul


def _sum_points(F, pt):
    """Coords [k, ...] → [1, ...] Jacobian sum, one add per fold step."""
    inf = (pt[0][:1], pt[1][:1], jnp.zeros_like(pt[2][:1]))
    return fold_sum(F, inf, tuple(c[None] for c in pt))


def sharded_rlc_g1_fn(mesh: Mesh):
    """Build the jitted sharded step: ([n,48]×3 G1 Jacobian, [n,64] scalar
    bits) sharded over `batch` → replicated [1,48]×3 Σ rᵢ·Pᵢ."""

    def per_device(xs, ys, zs, bits):
        scaled = pt_scalar_mul(DevFq, (xs, ys, zs), bits)
        part = _sum_points(DevFq, scaled)  # [1, 48] each coord
        gathered = tuple(
            lax.all_gather(c[0], "batch", tiled=False) for c in part
        )  # [n_devices, 48]
        return _sum_points(DevFq, gathered)

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P("batch"), P("batch"), P("batch"), P("batch")),
        out_specs=(P("batch"), P("batch"), P("batch")),  # identical rows
        check_vma=False,
    )

    @jax.jit
    def rlc_sum(xs, ys, zs, bits):
        out = sharded(xs, ys, zs, bits)
        return tuple(c[:1] for c in out)

    return rlc_sum


@functools.cache
def build_sharded_bls(n_devices: int):
    devices = np.array(jax.devices()[:n_devices])
    mesh = Mesh(devices, ("batch",))
    fn = sharded_rlc_g1_fn(mesh)
    sharding = NamedSharding(mesh, P("batch"))
    return mesh, fn, sharding


def dryrun_sharded_bls(mesh: Mesh) -> None:
    """One tiny sharded RLC step on `mesh`, cross-checked against the host
    bigint oracle. Raises on mismatch."""
    import random

    from ..crypto.bls12_381 import FQ, G1_GEN, pt_eq, pt_mul
    from ..crypto.bls12_381.curve import inf, pt_add as host_pt_add
    from .bls381 import g1_points_from_device, g1_points_to_device, scalars_to_bits

    n_devices = mesh.devices.size
    n = n_devices  # one point per device: the smallest real shard
    rng = random.Random(1234)
    pts = [pt_mul(FQ, G1_GEN, rng.randrange(1, 1 << 30)) for _ in range(n)]
    scalars = [rng.getrandbits(64) for _ in range(n)]

    fn = sharded_rlc_g1_fn(mesh)
    sharding = NamedSharding(mesh, P("batch"))
    xs, ys, zs = g1_points_to_device(pts)
    xs, ys, zs = (jax.device_put(c, sharding) for c in (xs, ys, zs))
    bits = jax.device_put(
        jnp.asarray(scalars_to_bits(scalars, 64)), sharding
    )
    got = g1_points_from_device(fn(xs, ys, zs, bits))[0]

    want = inf(FQ)
    for p, s in zip(pts, scalars):
        want = host_pt_add(FQ, want, pt_mul(FQ, p, s))
    assert pt_eq(FQ, got, want), "sharded RLC G1 sum mismatch vs host"
