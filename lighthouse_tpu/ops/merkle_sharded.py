"""Multi-chip sharded Merkleization: the distributed device step.

The validator-scale analog of the reference's batch parallelism (SURVEY.md
§2.9): the Merkle leaf array is sharded across the `batch` mesh axis, each
device hashes its subtree locally (pure VPU work over its HBM shard), the
per-device subtree roots ride ICI via `all_gather`, and the small top of the
tree is folded on every device redundantly (replicated compute beats a
round-trip). Scales to any power-of-two device count with zero host
involvement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sha256 import _compress, _IV, _PAD64


def _sha256_pairs_inline(nodes):
    """nodes [M, 8] u32 → parents [M//2, 8] u32 (M even, static)."""
    blocks = nodes.reshape(-1, 16)
    n = blocks.shape[0]
    iv = jnp.broadcast_to(jnp.asarray(_IV), (n, 8))
    st = _compress(iv, blocks)
    pad = jnp.broadcast_to(jnp.asarray(_PAD64), (n, 16))
    return _compress(st, pad)


def _reduce_to_root(nodes, depth: int):
    """Hash [2^depth, 8] down to [1, 8]: one fori_loop over the levels on a
    fixed-shape buffer (each level hashes the whole buffer's pairs and
    re-pads with zeros; row 0.. holds the live level), so the program
    holds one compression pair whatever the depth. An unrolled level loop
    compiles one program per level into the step."""
    if depth == 0:
        return nodes
    half = nodes.shape[0] // 2

    def level(_, buf):
        parents = _sha256_pairs_inline(buf)  # [half, 8]
        return jnp.concatenate([parents, jnp.zeros_like(parents)], axis=0)

    return lax.fori_loop(0, depth, level, nodes)[:1]


def sharded_merkle_root_fn(mesh: Mesh, per_device_leaves: int, n_devices: int):
    """Build a jitted fn: [N, 8] u32 leaves (N = n_devices * per_device_leaves,
    both powers of two) → [8] u32 Merkle root, sharded over `mesh`."""
    assert per_device_leaves & (per_device_leaves - 1) == 0
    assert n_devices & (n_devices - 1) == 0
    local_depth = (per_device_leaves - 1).bit_length()
    top_depth = (n_devices - 1).bit_length()

    def per_device(leaves_shard):
        # leaves_shard: [per_device_leaves, 8] local block
        subtree_root = _reduce_to_root(leaves_shard, local_depth)  # [1, 8]
        # ICI: gather every device's subtree root, fold the top replicated
        roots = lax.all_gather(
            subtree_root[0], "batch", tiled=False
        )  # [n_devices, 8]
        return _reduce_to_root(roots, top_depth)  # [1, 8]

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=P("batch", None),
        out_specs=P("batch", None),  # each device emits the (identical) root
        check_vma=False,
    )

    @jax.jit
    def merkle_root(leaves):
        out = sharded(leaves)  # [n_devices, 8] — identical rows
        return out[0]

    return merkle_root


@functools.cache
def build_sharded_merkle(n_devices: int, per_device_leaves: int):
    """Convenience: mesh over the first n_devices + the jitted root fn."""
    import numpy as np

    devices = np.array(jax.devices()[:n_devices])
    mesh = Mesh(devices, ("batch",))
    fn = sharded_merkle_root_fn(mesh, per_device_leaves, n_devices)
    sharding = NamedSharding(mesh, P("batch", None))
    return mesh, fn, sharding
