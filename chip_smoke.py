"""Smoke run of the beacon node's device path on one TPU.

    python chip_smoke.py              # phases 1-6 on one chip
    python chip_smoke.py --four-chips # only the sharded kernels, on 4 chips

Everything runs in this one process (a chip belongs to one process), the
cheap phases first, so that a slow compile late in the run cannot hide
whether the earlier phases work:

  1. device       JAX must see a TPU: there is no CPU path.
  2. node         a ClientBuilder node with the tpu BLS backend, the
                  in-process validator client (real signatures), the beacon
                  processor and the packaged mainnet KZG setup, driven across
                  epoch boundaries on a manual slot clock; its blocks are
                  replayed through a host-backend chain to identical roots.
  3. bls          1024 signature sets over 64-key committees through the tpu
                  backend: valid → True, one swapped signature → False, and
                  the host backend agrees on both.
  4. kzg          6 blobs × 4096 field elements on the mainnet setup: device
                  commitments and proofs (Pippenger MSM) equal the host's,
                  the batch verifies, a tampered proof fails, host agrees.
  5. tree_hash    a 1M-validator state root through the device tree lane
                  equals the host root; a 2^20-leaf device Merkle root
                  equals hashlib's.
  6. epoch_sweep  the device sweep over a non-genesis epoch of a 1M state is
                  bit-exact with the numpy sweep.

Each phase runs twice and prints its cold and warm seconds (information,
not metrics). Any failed check raises: the script then exits non-zero and
prints no result line. The last line of a passing run is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys
import time

import numpy as np

NODE_VALIDATORS = 256  # minimal preset: 32 attesters per slot = one full chunk
NODE_EPOCHS = 4
BLS_SETS, BLS_COMMITTEE = 1024, 64
KZG_BLOBS, KZG_DOMAIN = 6, 4096
REGISTRY_SIZE = 1 << 20
MERKLE_LEAVES = 1 << 20
_REPO = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def cold_and_warm(name: str, fn):
    """Run fn twice; print both times; return the cold run's result."""
    t0 = time.perf_counter()
    out = fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    fn()
    warm = time.perf_counter() - t0
    log(f"[{name}] cold {cold:.1f} s, warm {warm:.1f} s")
    return out


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------


def phase_device(expect: int | None = None) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}
    log(f"[device] {info}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform {d.platform!r}")
    if expect is not None:
        check(len(devices) >= expect, f"need {expect} devices, have {len(devices)}")
        info["count"] = expect
    return info


# ---------------------------------------------------------------------------
# 2. node
# ---------------------------------------------------------------------------


def _counter(name: str, **labels) -> float:
    from lighthouse_tpu.metrics import REGISTRY

    return REGISTRY.counter(name).value(**labels)


def _drive_node(validators: int, epochs: int):
    """Boot a tpu-backend node with an in-process VC and drive it; returns
    (blocks oldest-first, head root, head state root, finalized epoch,
    verify batch sizes, config)."""
    from dataclasses import replace

    from lighthouse_tpu.client import ClientBuilder, ClientConfig
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    cfg = ClientConfig(
        spec=replace(minimal_spec(), altair_fork_epoch=0),
        E=E,
        validator_count=validators,
        validate=True,
        manual_slot_clock=True,
        bls_backend="tpu",
        kzg="default",
        http_port=None,
        sync_service_interval=None,
    )
    client = ClientBuilder(cfg).build().start()
    tpu = bls.get_backend()
    check(tpu.name == "tpu", "node did not select the tpu backend")
    sizes: list[int] = []
    verify = tpu.verify_signature_sets

    def counting(sets, rng=None):
        sets = list(sets)
        sizes.append(len(sets))
        return verify(sets, rng)

    tpu.verify_signature_sets = counting
    last = epochs * E.SLOTS_PER_EPOCH
    try:
        for slot in range(1, last + 1):
            client.on_slot(slot)
            client.network.processor.drain()
        chain = client.chain
        blocks = []
        root = chain.head_root
        while True:
            blk = chain.store.get_block(root)
            if blk is None or int(blk.message.slot) == 0:
                break
            blocks.append(blk)
            root = bytes(blk.message.parent_root)
        blocks.reverse()
        check(
            [int(b.message.slot) for b in blocks] == list(range(1, last + 1)),
            "a produced block is missing from the canonical chain",
        )
        head_state_root = chain.head_state.hash_tree_root()
        return (
            blocks,
            chain.head_root,
            head_state_root,
            int(chain.finalized_checkpoint.epoch),
            sizes,
            cfg,
        )
    finally:
        del tpu.verify_signature_sets
        client.stop()


def _replay(blocks, cfg, backend: str):
    """Import the node's blocks into a fresh chain on `backend`; every
    post-state root must equal the one the block carries."""
    from dataclasses import replace

    from lighthouse_tpu.client import ClientBuilder

    replay_cfg = replace(
        cfg, bls_backend=backend, validate=False, network_port=None, kzg="none"
    )
    client = ClientBuilder(replay_cfg).build()
    try:
        for blk in blocks:
            client.slot_clock.set_slot(int(blk.message.slot))
            client.chain.process_block(blk)
            check(
                client.chain.head_state.hash_tree_root()
                == bytes(blk.message.state_root),
                f"{backend} replay diverged at slot {int(blk.message.slot)}",
            )
        return client.chain.head_root, client.chain.head_state.hash_tree_root()
    finally:
        client.stop()


def _pool_fork_matches_inline():
    """Signatures from the forked host pool (forked by the process that
    holds the chip) equal the inline ones; the pool really forked."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.parallel import host_pool

    kps = bls.interop_keypairs(64)
    msgs = [hashlib.sha256(bytes([i % 4])).digest() for i in range(64)]
    sks = [kp.sk for kp in kps]
    host = bls._BACKENDS["host"]
    forks = _counter("bls_pool_tasks_total", mode="fork")
    pooled = host.sign_batch(sks, msgs)
    forked = _counter("bls_pool_tasks_total", mode="fork") - forks
    prior = os.environ.get(host_pool.ENV_VAR)
    os.environ[host_pool.ENV_VAR] = "1"
    try:
        inline = host.sign_batch(sks, msgs)
    finally:
        if prior is None:
            del os.environ[host_pool.ENV_VAR]
        else:
            os.environ[host_pool.ENV_VAR] = prior
    check(pooled == inline, "forked host pool signed differently from inline")
    size = host_pool.get_pool().size
    check(forked > 0 or size <= 1, "the host pool never forked")
    return size, forked


def phase_node() -> None:
    from lighthouse_tpu.crypto import bls

    forks = _counter("bls_pool_tasks_total", mode="fork")
    batches = _counter("bls_device_batches_total")
    host_batches = [_counter("bls_batch_verify_total", path=p) for p in ("msm", "serial")]
    t0 = time.perf_counter()
    blocks, head, head_state_root, finalized, sizes, cfg = _drive_node(
        NODE_VALIDATORS, NODE_EPOCHS
    )
    cold = time.perf_counter() - t0
    node_forks = _counter("bls_pool_tasks_total", mode="fork") - forks
    check(_counter("bls_device_batches_total") > batches, "no device BLS batch ran")
    check(finalized >= 2, f"finality did not advance (finalized epoch {finalized})")
    check(
        max(sizes) >= bls.DEFAULT_DEVICE_CHUNK,
        f"largest batch {max(sizes)} < one device chunk",
    )
    # warm re-run of the device work: the same blocks imported by a fresh
    # tpu-backend chain, every kernel already compiled
    t0 = time.perf_counter()
    check(_replay(blocks, cfg, "tpu") == (head, head_state_root), "tpu replay head differs")
    warm = time.perf_counter() - t0
    check(
        [_counter("bls_batch_verify_total", path=p) for p in ("msm", "serial")]
        == host_batches,
        "a tpu-backend batch was verified on the host",
    )
    check(_replay(blocks, cfg, "host") == (head, head_state_root), "host replay head differs")
    pool_size, forked = _pool_fork_matches_inline()
    log(
        f"[node] cold {cold:.1f} s (boot + {len(blocks)} slots driven), "
        f"warm {warm:.1f} s (tpu-backend replay of its blocks)"
    )
    log(
        f"[node] validators {NODE_VALIDATORS}, blocks {len(blocks)}, "
        f"finalized epoch {finalized}, verify calls {len(sizes)}, "
        f"largest batch {max(sizes)} sets, host replay roots equal; "
        f"host pool of {pool_size}: {node_forks:.0f} forked tasks in the "
        f"node, {forked:.0f} more signed = inline"
    )


# ---------------------------------------------------------------------------
# 3. bls
# ---------------------------------------------------------------------------


def phase_bls() -> None:
    import bench
    from lighthouse_tpu.crypto import bls

    # the bls_batch_verify_1k sets: bench.py's generator and committed cache
    sets = bench._make_sets(bls, BLS_SETS, BLS_COMMITTEE)
    bad = list(sets)
    bad[BLS_SETS // 2] = bls.SignatureSet(
        sets[BLS_SETS // 2 + 1].signature,
        sets[BLS_SETS // 2].pubkeys,
        sets[BLS_SETS // 2].message,
    )
    tpu = bls._BACKENDS["tpu"]
    host = bls._BACKENDS["host"]
    ok = cold_and_warm(
        "bls", lambda: tpu.verify_signature_sets(sets, random.Random(1))
    )
    check(ok is True, "device rejected a valid 1024-set batch")
    check(
        tpu.verify_signature_sets(bad, random.Random(2)) is False,
        "device accepted a batch with a swapped signature",
    )
    check(host.verify_signature_sets(sets, random.Random(3)) is True, "host rejected")
    check(host.verify_signature_sets(bad, random.Random(4)) is False, "host accepted")
    log(f"[bls] {BLS_SETS} sets x {BLS_COMMITTEE} keys: valid True, swapped False, host agrees")


# ---------------------------------------------------------------------------
# 4. kzg
# ---------------------------------------------------------------------------


def phase_kzg() -> None:
    from lighthouse_tpu.crypto.kzg import FR_MODULUS, Kzg, TrustedSetup

    setup = TrustedSetup.default()
    check(setup.n == KZG_DOMAIN, f"expected the mainnet setup, got n={setup.n}")
    rng = random.Random(33)
    blobs = [
        b"".join(rng.randrange(FR_MODULUS).to_bytes(32, "big") for _ in range(setup.n))
        for _ in range(KZG_BLOBS)
    ]
    host = Kzg(setup, device=False)
    dev = Kzg(setup, device=True)
    # device commitments and proofs go through the Pippenger MSM kernel
    cs = cold_and_warm(
        "kzg_commit", lambda: [dev.blob_to_kzg_commitment(b) for b in blobs]
    )
    proofs = [dev.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, cs)]
    check(cs[0] == host.blob_to_kzg_commitment(blobs[0]), "device commitment != host")
    check(
        proofs[0] == host.compute_blob_kzg_proof(blobs[0], cs[0]),
        "device proof != host",
    )
    # the same blobs' host-engine commitments and proofs, as bench.py
    # generated and committed them
    import pickle

    with open(os.path.join(_REPO, ".bench_cache", f"kzg_v1_{KZG_BLOBS}x{setup.n}.pkl"), "rb") as f:
        check(pickle.load(f) == (cs, proofs), "device commitments/proofs != committed host ones")
    ok = cold_and_warm(
        "kzg_verify", lambda: dev.verify_blob_kzg_proof_batch(blobs, cs, proofs)
    )
    check(ok is True, "device rejected a valid blob batch")
    bad = list(proofs)
    bad[-1] = proofs[0]
    check(not dev.verify_blob_kzg_proof_batch(blobs, cs, bad), "device accepted a tampered proof")
    check(host.verify_blob_kzg_proof_batch(blobs, cs, proofs), "host rejected")
    check(not host.verify_blob_kzg_proof_batch(blobs, cs, bad), "host accepted")
    log(f"[kzg] {KZG_BLOBS} blobs x {setup.n}: commitments/proofs equal host, valid True, tampered False, host agrees")


# ---------------------------------------------------------------------------
# 5. tree hash, 6. epoch sweep (one 1M-validator state)
# ---------------------------------------------------------------------------


def _hashlib_root(leaves: bytes) -> bytes:
    nodes = [leaves[i : i + 32] for i in range(0, len(leaves), 32)]
    while len(nodes) > 1:
        nodes = [
            hashlib.sha256(nodes[i] + nodes[i + 1]).digest()
            for i in range(0, len(nodes), 2)
        ]
    return nodes[0]


def phase_tree_hash(state) -> None:
    import jax

    from lighthouse_tpu.ops.sha256 import bytes_to_words, merkle_tree_levels, words_to_bytes
    from lighthouse_tpu.ssz import cached_tree_hash

    leaves = np.random.default_rng(5).integers(
        0, 256, MERKLE_LEAVES * 32, dtype=np.uint8
    ).tobytes()
    words = bytes_to_words(leaves)
    root = cold_and_warm(
        "merkle_2^20",
        lambda: words_to_bytes(merkle_tree_levels(jax.device_put(words))[0]),
    )
    check(root == _hashlib_root(leaves), "device Merkle root != hashlib")

    def state_root(device: bool):
        os.environ["LIGHTHOUSE_TPU_DEVICE_TREE"] = "1" if device else "0"
        try:
            state.__dict__.pop("_thc_cache", None)  # a cold build each time
            return state.hash_tree_root()
        finally:
            del os.environ["LIGHTHOUSE_TPU_DEVICE_TREE"]

    builds = cached_tree_hash.stats()["device_builds"]
    dev_root = cold_and_warm("state_root_1m", lambda: state_root(True))
    check(cached_tree_hash.stats()["device_builds"] > builds, "the device tree lane never ran")
    check(dev_root == state_root(False), "device state root != host state root")
    log(f"[tree_hash] 2^20-leaf root = hashlib; {len(state.validators)}-validator state root device = host")


def phase_epoch_sweep(state, spec, E) -> None:
    from lighthouse_tpu.state_processing.altair import (
        EpochArrays,
        _device_sweep_applicable,
    )
    from lighthouse_tpu.state_processing.per_epoch import process_epoch

    check(_device_sweep_applicable(state, EpochArrays(state, E), spec, E), "sweep not applicable")

    def run(device: bool):
        st = state.copy()
        os.environ["LIGHTHOUSE_TPU_DEVICE_EPOCH_SWEEP"] = "1" if device else "0"
        try:
            process_epoch(st, spec, E)
        finally:
            del os.environ["LIGHTHOUSE_TPU_DEVICE_EPOCH_SWEEP"]
        return st

    # the host path runs an epoch_stage_inactivity span; the device sweep
    # replaces that stage, so its count must not move
    host_stage = _span_count("epoch_stage_inactivity")
    dev = cold_and_warm("epoch_sweep_1m", lambda: run(True))
    check(_span_count("epoch_stage_inactivity") == host_stage, "the host sweep ran")
    ref = run(False)
    check(_span_count("epoch_stage_inactivity") == host_stage + 1, "no host reference ran")
    check(list(dev.balances) == list(ref.balances), "balances differ from numpy")
    check(
        list(dev.inactivity_scores) == list(ref.inactivity_scores),
        "inactivity scores differ from numpy",
    )
    check(dev.hash_tree_root() == ref.hash_tree_root(), "post-epoch roots differ")
    log(f"[epoch_sweep] {len(state.validators)} validators, epoch {int(state.slot) // E.SLOTS_PER_EPOCH}: device = numpy")


def _span_count(name: str) -> int:
    from lighthouse_tpu.metrics import REGISTRY

    return REGISTRY.histogram(f"trace_span_seconds_{name}").count


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _bytes_per_device(arr) -> dict:
    return {str(s.device.id): int(s.data.nbytes) for s in arr.addressable_shards}


def phase_four_chips() -> None:
    import jax
    from jax.sharding import Mesh

    from lighthouse_tpu.crypto.bls12_381 import FQ, G1_GEN, pt_eq, pt_mul
    from lighthouse_tpu.crypto.bls12_381.curve import inf, pt_add
    from lighthouse_tpu.ops.bls381 import g1_points_from_device, g1_points_to_device, scalars_to_bits
    from lighthouse_tpu.ops.bls381_sharded import sharded_rlc_g1_fn
    from lighthouse_tpu.ops.merkle_sharded import build_sharded_merkle
    from lighthouse_tpu.ops.sha256 import bytes_to_words, merkle_tree_levels, words_to_bytes
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = 4
    leaves = np.random.default_rng(7).integers(
        0, 256, MERKLE_LEAVES * 32, dtype=np.uint8
    ).tobytes()
    words = bytes_to_words(leaves)
    _, fn, sharding = build_sharded_merkle(n_dev, MERKLE_LEAVES // n_dev)
    sharded = jax.device_put(words, sharding)
    log(f"[four_chips] Merkle leaf bytes per device {_bytes_per_device(sharded)}")
    root4 = cold_and_warm("merkle_sharded_4", lambda: words_to_bytes(jax.block_until_ready(fn(sharded))))
    one = jax.device_put(words, jax.devices()[0])
    root1 = words_to_bytes(merkle_tree_levels(one)[0])
    check(root4 == root1, "sharded Merkle root != one-device root")

    n = 32 * n_dev
    rng = random.Random(1234)
    pts = [pt_mul(FQ, G1_GEN, rng.randrange(1, 1 << 30)) for _ in range(n)]
    scalars = [rng.getrandbits(64) for _ in range(n)]
    host_pts = g1_points_to_device(pts)
    bits = scalars_to_bits(scalars, 64)

    def rlc_sum(devices):
        mesh = Mesh(np.array(devices), ("batch",))
        shard = NamedSharding(mesh, P("batch"))
        args = [jax.device_put(c, shard) for c in (*host_pts, bits)]
        out = sharded_rlc_g1_fn(mesh)(*args)
        return args, g1_points_from_device(out)[0]

    args4, got4 = cold_and_warm("rlc_g1_sharded_4", lambda: rlc_sum(jax.devices()[:n_dev]))
    log(f"[four_chips] RLC point bytes per device {_bytes_per_device(args4[0])}")
    _, got1 = rlc_sum(jax.devices()[:1])
    want = inf(FQ)
    for p, s in zip(pts, scalars):
        want = pt_add(FQ, want, pt_mul(FQ, p, s))
    check(pt_eq(FQ, got4, got1), "sharded RLC sum != one-device sum")
    check(pt_eq(FQ, got4, want), "sharded RLC sum != host oracle")
    log("[four_chips] sharded Merkle root = one-device root; sharded RLC G1 sum = one-device sum = host")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the sharded Merkle root and RLC G1 sum on 4 chips",
    )
    args = ap.parse_args(argv)
    sys.path.insert(0, _REPO)
    t_start = time.perf_counter()
    info = phase_device(expect=4 if args.four_chips else None)

    from lighthouse_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.four_chips:
        phase_four_chips()
    else:
        phase_node()
        phase_bls()
        phase_kzg()
        import bench

        state, spec, E = bench._build_epoch_state(REGISTRY_SIZE, resident=True)
        phase_tree_hash(state)
        phase_epoch_sweep(state, spec, E)
    log(f"[total] {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
