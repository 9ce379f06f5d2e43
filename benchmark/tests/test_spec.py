"""The benchmark's spec pieces agree with the program's: the shuffle and
committees, and the SSZ bytes and roots of an attestation."""

import numpy as np

from benchmark import spec


def test_shuffle_matches_spec_single_index_and_program():
    from lighthouse_tpu.state_processing.shuffle import _shuffled_positions

    seed = spec.sha256(b"seed")
    for n in (1000, 4096):
        perm = spec.shuffled_positions(n, seed, 90)
        assert sorted(perm.tolist()) == list(range(n))
        assert np.array_equal(perm, np.asarray(_shuffled_positions(n, seed, 90)))


def test_attestation_bytes_decode_in_the_program():
    from lighthouse_tpu.types.containers import build_types
    from lighthouse_tpu.types.eth_spec import MainnetEthSpec as E

    t = build_types(E)
    root = b"\x07" * 32
    data = spec.attestation_data_bytes(37, 5, root, (0, bytes(32)), (1, root))
    raw = spec.attestation_bytes(512, 300, data, b"\xaa" * 96)
    att = t.Attestation.deserialize(raw)
    assert int(att.data.slot) == 37 and int(att.data.index) == 5
    assert [i for i, b in enumerate(att.aggregation_bits) if b] == [300]
    assert len(att.aggregation_bits) == 512
    assert att.data.hash_tree_root() == spec.attestation_data_root(data)
    assert spec.parse_attestation(raw) == ([300], 512, data, b"\xaa" * 96)


def test_state_and_anchor_roots_match_the_program():
    from benchmark import registry

    cfg = {"validators": 1 << 10, "key_base": "9e3779b97f4a7c15", "genesis_time": 1606824023,
           "randao_mix": "42" * 32, "genesis_validators_root": "4b" * 32}
    _, E, state = registry.anchor_state(cfg, registry.keys(cfg))
    assert spec.hash_tree_root(spec.BEACON_STATE_DENEB, state) == bytes(state.hash_tree_root())
    header = state.latest_block_header.copy()
    header.state_root = state.hash_tree_root()
    assert spec.anchor_block_root(state) == bytes(header.hash_tree_root())


def test_merkleize_pads_to_the_limit():
    a, b = b"\x01" * 32, b"\x02" * 32
    assert spec.merkleize([a, b]) == spec.sha256(a + b)
    assert spec.merkleize([a], 4) == spec.sha256(spec.sha256(a + bytes(32)) + spec.sha256(bytes(64)))
    assert spec.merkleize([], 1) == bytes(32)
