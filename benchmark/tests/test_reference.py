"""The reference's own pieces against public vectors and against each
other: Keccak-256 and RLP, the KZG setup and blob pool, the shuffle, and
the SSZ of a full block read back by the program's decoder."""

import pytest

from benchmark import bls, deneb, kzg, spec, transition
from benchmark.keccak import EMPTY_OMMERS_HASH, EMPTY_TRIE_ROOT, keccak256, rlp


def test_keccak_and_rlp_vectors():
    assert keccak256(b"").hex() == "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    assert EMPTY_TRIE_ROOT.hex() == "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421"
    assert EMPTY_OMMERS_HASH.hex() == "1dcc4de8dec75d7aab85b567b6ccd41ad312451b948a7413f0a142fd40d49347"
    assert rlp(b"dog") == b"\x83dog"
    assert rlp([b"cat", b"dog"]) == b"\xc8\x83cat\x83dog"
    assert rlp(0) == b"\x80" and rlp(1024) == b"\x82\x04\x00"


@pytest.fixture(scope="module")
def tau():
    return kzg.tau_g1(kzg.lagrange_points())


def test_lagrange_points_sum_to_the_generator():
    # sum_i L_i(tau) = 1 for any tau
    assert kzg.g1_msm(kzg.lagrange_points(), [1] * kzg.FIELD_ELEMENTS_PER_BLOB) == bls.G1


def test_blob_pool_proofs(tau):
    pool = kzg.blob_pool("test", 2, tau)
    (b0, c0, p0), (b1, c1, p1) = pool
    assert kzg.proof_valid(b0, c0, p0, tau) and kzg.proof_valid(b1, c1, p1, tau)
    assert not kzg.proof_valid(b0, c0, p1, tau)
    assert not kzg.proof_valid(b0, c1, p0, tau)


def test_shuffled_index_matches_the_committee_shuffle():
    seed = spec.attester_seed(0, b"\x42" * 32)
    perm = spec.shuffled_positions(1000, seed, 90)
    assert [transition.compute_shuffled_index(i, 1000, seed, 90) for i in range(0, 1000, 97)] == [
        int(perm[i]) for i in range(0, 1000, 97)
    ]


def test_tree_updates_match_a_full_rehash():
    chunks = [bytes([i]) * 32 for i in range(13)]
    tree = deneb.Tree(list(chunks), 64)
    chunks[5] = b"\x77" * 32
    tree.set(5, chunks[5])
    assert tree.root() == spec.merkleize(chunks, 64)


def test_block_ssz_is_the_programs():
    from lighthouse_tpu.types.containers import build_types
    from lighthouse_tpu.types.eth_spec import MainnetEthSpec

    T = build_types(MainnetEthSpec)
    att = {"aggregation_bits": [1, 0, 1] * 100,
           "data": {"slot": 3, "index": 2, "beacon_block_root": b"\x01" * 32,
                    "source": {"epoch": 0, "root": bytes(32)},
                    "target": {"epoch": 0, "root": b"\x02" * 32}},
           "signature": b"\x03" * 96}
    payload = {name: (bytes(20) if name == "fee_recipient" else bytes(256) if name == "logs_bloom"
                      else b"" if name == "extra_data" else 0 if t[0] == "uint" else b"\x05" * 32)
               for name, t in deneb.EXECUTION_PAYLOAD if name not in ("transactions", "withdrawals")}
    payload.update(transactions=[b"\x03abc"], withdrawals=[])
    body = {"randao_reveal": b"\x04" * 96,
            "eth1_data": {"deposit_root": b"\x06" * 32, "deposit_count": 9, "block_hash": b"\x07" * 32},
            "graffiti": b"\x08" * 32, "proposer_slashings": [], "attester_slashings": [],
            "attestations": [att, att], "deposits": [], "voluntary_exits": [],
            "sync_aggregate": {"sync_committee_bits": [1, 0] * 256,
                               "sync_committee_signature": b"\x09" * 96},
            "execution_payload": payload, "bls_to_execution_changes": [],
            "blob_kzg_commitments": [b"\x0a" * 48, b"\x0b" * 48]}
    block = {"slot": 7, "proposer_index": 11, "parent_root": b"\x0c" * 32,
             "state_root": b"\x0d" * 32, "body": body}
    signed = {"message": block, "signature": b"\x0e" * 96}
    raw = deneb.serialize(deneb.SIGNED_BLOCK, signed)
    got = T.SignedBeaconBlockDeneb.deserialize(raw)
    assert got.serialize() == raw
    assert got.message.hash_tree_root() == deneb.hash_tree_root(deneb.BLOCK, block)
    assert got.message.body.hash_tree_root() == deneb.hash_tree_root(deneb.BODY, body)
    proof = deneb.commitment_inclusion_proof(body, 1)
    assert len(proof) == deneb.KZG_COMMITMENT_INCLUSION_PROOF_DEPTH
