"""The validator registry and the anchor state the node boots from.

Keys: sk_v = s0 + v, so pk_v = pk_(v-1) + G1 — a million distinct keys for
the price of a million affine additions (no key repeats inside a committee,
so no committee sum ever adds a point to itself). The keys do not depend on
the seed, so the first run in a checkout writes them under
`benchmark/.cache/` and later runs read them back.

The anchor state is the program's own BeaconState type filled the way a
checkpoint-synced node receives it: every validator active at 32 ETH, the
registry's sync committees, and the configuration's genesis validators
root and fork versions.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

from . import bls

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")


def keys(cfg: dict) -> np.ndarray:
    """[n, 3, 48] uint8: compressed pubkey, affine x, affine y (big-endian)
    of every validator, built once per checkout and then read back."""
    n = cfg["validators"]
    path = os.path.join(CACHE_DIR, f"keys_{n}_{cfg['key_base']}.npy")
    if os.path.exists(path):
        arr = np.load(path)
        if arr.shape == (n, 3, 48):
            return arr
    start = bls.g1_mul_affine(bls.G1, int(cfg["key_base"], 16))
    pts = bls.g1_affine_chain(start, bls.G1, n)
    arr = np.frombuffer(
        b"".join(
            bls.g1_compress(p) + p[0].to_bytes(48, "big") + p[1].to_bytes(48, "big")
            for p in pts
        ),
        dtype=np.uint8,
    ).reshape(n, 3, 48)
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npy"
    np.save(tmp, arr)
    os.replace(tmp, path)
    return arr


def load_pubkey_cache(key_arr: np.ndarray, used) -> int:
    """Put every decompressed, subgroup-checked key in the node's pubkey
    cache, as a node restarting from its database has them, and return how
    many of the keys `used` by the run the cache did not hold.

    The program has no public loader (see PERF.md), so this first fills
    the cache's entries directly (encoding → [affine point with z = 1,
    validated]). Then every used key goes through the program's public
    path, PublicKey.from_bytes(...).validate(): a cache hit where the fill
    took, and where the cache has another shape the full decompression
    and subgroup check, paid here in set-up and never in the window."""
    from lighthouse_tpu.crypto import bls as node_bls
    from lighthouse_tpu.metrics import REGISTRY

    raw = key_arr.tobytes()
    lru = getattr(getattr(node_bls, "_PK_CACHE", None), "_lru", None)
    if lru is not None and getattr(lru, "maxsize", 0) >= len(key_arr):
        for i in range(len(key_arr)):
            o = i * 144
            x = int.from_bytes(raw[o + 48 : o + 96], "big")
            y = int.from_bytes(raw[o + 96 : o + 144], "big")
            lru.setdefault(raw[o : o + 48], [(x, y, 1), True])

    def misses():
        counts = REGISTRY.counter("bls_cache_misses_total").values()
        return sum(n for key, n in counts.items() if dict(key).get("cache", "").startswith("pubkey"))

    before = misses()
    for v in used:
        if not node_bls.PublicKey.from_bytes(raw[v * 144 : v * 144 + 48]).validate():
            raise RuntimeError(f"the program rejects validator {v}'s key")
    return int(misses() - before)


def anchor_root(cfg: dict, state) -> bytes:
    """The root of the anchor block, computed by the benchmark's own SSZ
    from the state it hands the node (spec.anchor_block_root). It does not
    depend on the seed, so the first run in a checkout writes it under
    `benchmark/.cache/` and later runs read it back."""
    from . import spec

    digest = hashlib.sha256(repr(sorted(cfg.items())).encode()).hexdigest()[:16]
    path = os.path.join(CACHE_DIR, f"anchor_root_{cfg['validators']}_{digest}.hex")
    if os.path.exists(path):
        with open(path) as f:
            return bytes.fromhex(f.read().strip())
    root = spec.anchor_block_root(state)
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        f.write(root.hex())
    os.replace(tmp, path)
    return root


def anchor_state(cfg: dict, key_arr: np.ndarray):
    """(spec, E, state): the program's BeaconState at slot 0 of a chain
    whose forks up to cfg['fork'] are active from epoch 0."""
    from dataclasses import replace

    from lighthouse_tpu.crypto import bls as node_bls
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.state_processing.altair import get_next_sync_committee
    from lighthouse_tpu.types.chain_spec import mainnet_spec
    from lighthouse_tpu.types.containers import build_types
    from lighthouse_tpu.types.eth_spec import MainnetEthSpec as E

    n = cfg["validators"]
    spec = replace(
        mainnet_spec(),
        altair_fork_epoch=0,
        bellatrix_fork_epoch=0,
        capella_fork_epoch=0,
        deneb_fork_epoch=0,
    )
    previous = node_bls.backend_name()
    node_bls.set_backend("fake_crypto")  # the placeholder deposits' keys
    try:
        state = interop_genesis_state(
            node_bls.interop_keypairs(16),
            cfg["genesis_time"],
            bytes.fromhex(cfg["randao_mix"]),
            spec,
            E,
        )
    finally:
        node_bls.set_backend(previous)
    t = build_types(E)
    proto = t.Validator(
        pubkey=bytes(48),
        withdrawal_credentials=bytes(32),
        effective_balance=E.MAX_EFFECTIVE_BALANCE,
        slashed=False,
        activation_eligibility_epoch=0,
        activation_epoch=0,
        exit_epoch=2**64 - 1,
        withdrawable_epoch=2**64 - 1,
    ).__dict__
    raw = key_arr[:, 0, :].tobytes()
    validators = []
    for i in range(n):
        pk = raw[i * 48 : (i + 1) * 48]
        v = t.Validator.__new__(t.Validator)
        v.__dict__.update(proto)
        v.__dict__["pubkey"] = pk
        v.__dict__["withdrawal_credentials"] = b"\x00" + hashlib.sha256(pk).digest()[1:]
        validators.append(v)
    for cached in ("_thc_cache", "_registry_columns", "_lh_caches"):
        state.__dict__.pop(cached, None)
    object.__setattr__(state, "validators", validators)
    object.__setattr__(state, "balances", [E.MAX_EFFECTIVE_BALANCE] * n)
    object.__setattr__(state, "previous_epoch_participation", bytearray(n))
    object.__setattr__(state, "current_epoch_participation", bytearray(n))
    object.__setattr__(state, "inactivity_scores", [0] * n)
    state.eth1_data.deposit_count = n
    state.eth1_deposit_index = n
    state.genesis_validators_root = bytes.fromhex(cfg["genesis_validators_root"])
    committee = get_next_sync_committee(state, E)
    state.current_sync_committee = committee
    state.next_sync_committee = committee.copy()
    return spec, E, state
