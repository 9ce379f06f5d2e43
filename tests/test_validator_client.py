"""Validator client services + EIP-3076 slashing protection.

Mirrors validator_client tests: duties lookup, per-slot attest/propose
against an in-process beacon node, slashing refusals, interchange
import/export, doppelganger gating. The VC (not the harness) drives a
chain to finality in the e2e."""

from dataclasses import replace

import pytest

from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
from lighthouse_tpu.validator_client import ValidatorClient
from lighthouse_tpu.validator_client.slashing_protection import (
    NotSafe,
    SlashingDatabase,
)


# --- slashing protection ----------------------------------------------------


def test_block_proposal_protection():
    db = SlashingDatabase()
    pk = b"\xaa" * 48
    db.register_validator(pk)
    db.check_and_insert_block_proposal(pk, 10, b"\x01" * 32)
    # same slot + same root: idempotent
    db.check_and_insert_block_proposal(pk, 10, b"\x01" * 32)
    # same slot, different root: double proposal
    with pytest.raises(NotSafe):
        db.check_and_insert_block_proposal(pk, 10, b"\x02" * 32)
    # lower slot: refused
    with pytest.raises(NotSafe):
        db.check_and_insert_block_proposal(pk, 9, b"\x03" * 32)
    db.check_and_insert_block_proposal(pk, 11, b"\x04" * 32)


def test_attestation_protection():
    db = SlashingDatabase()
    pk = b"\xbb" * 48
    db.register_validator(pk)
    db.check_and_insert_attestation(pk, 2, 3, b"\x01" * 32)
    db.check_and_insert_attestation(pk, 3, 4, b"\x02" * 32)
    # double vote (same target, different root)
    with pytest.raises(NotSafe):
        db.check_and_insert_attestation(pk, 2, 4, b"\x03" * 32)
    # surround: (1, 5) surrounds (3, 4)
    with pytest.raises(NotSafe):
        db.check_and_insert_attestation(pk, 1, 5, b"\x04" * 32)
    # surrounded: with (2,3) and (3,4) recorded, (3.., ..) inside an
    # existing span — craft (2,3)-surrounding first then test inner
    db.check_and_insert_attestation(pk, 4, 7, b"\x05" * 32)
    with pytest.raises(NotSafe):
        db.check_and_insert_attestation(pk, 5, 6, b"\x06" * 32)
    # unregistered validator
    with pytest.raises(NotSafe):
        db.check_and_insert_attestation(b"\xcc" * 48, 1, 2, b"\x00" * 32)


def test_interchange_roundtrip():
    db = SlashingDatabase()
    pk = b"\xdd" * 48
    db.register_validator(pk)
    db.check_and_insert_block_proposal(pk, 5, b"\x01" * 32)
    db.check_and_insert_attestation(pk, 1, 2, b"\x02" * 32)
    gvr = b"\x99" * 32
    doc = db.export_interchange(gvr)
    assert doc["metadata"]["interchange_format_version"] == "5"

    db2 = SlashingDatabase()
    db2.import_interchange(doc, gvr)
    # imported history still protects
    with pytest.raises(NotSafe):
        db2.check_and_insert_block_proposal(pk, 5, b"\x07" * 32)
    with pytest.raises(NotSafe):
        db2.check_and_insert_attestation(pk, 1, 2, b"\x08" * 32)
    # wrong genesis root refused
    with pytest.raises(NotSafe):
        SlashingDatabase().import_interchange(doc, b"\x00" * 32)


# --- validator client e2e ---------------------------------------------------


def _vc_setup(validator_count=16):
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=validator_count)
    vc = ValidatorClient(h.chain, h.keypairs, spec, E)
    return h, vc


def test_duties_cover_every_managed_validator():
    h, vc = _vc_setup()
    duties = vc.duties_service.attester_duties(0)
    assert sorted(d.validator_index for d in duties) == list(range(16))
    # every slot is a valid epoch-0 slot
    assert all(0 <= d.slot < E.SLOTS_PER_EPOCH for d in duties)


def test_vc_drives_chain_to_finality():
    """The VC proposes and attests for 4 epochs; finality advances — the
    block/attestation path runs through ValidatorStore signing + slashing
    protection instead of the harness's direct signing."""
    h, vc = _vc_setup()
    for slot in range(1, 4 * E.SLOTS_PER_EPOCH + 1):
        h.slot_clock.set_slot(slot)
        root = vc.on_slot(slot)
        assert root is not None, f"no proposal at slot {slot} (all keys managed)"
    assert h.finalized_epoch >= 2
    # slashing DB recorded every proposal + attestation
    db = vc.store.slashing_db
    pk0 = h.keypairs[0].pk.to_bytes()
    with pytest.raises(NotSafe):
        db.check_and_insert_block_proposal(pk0, 1, b"\x00" * 32)


def test_vc_sync_committee_and_preparation_services():
    """Real crypto: the VC's SyncCommitteeService signs head roots into
    the chain's sync-message pool; the next produced block carries a
    non-empty, spec-valid SyncAggregate. PreparationService registers fee
    recipients via prepare_beacon_proposer (sync_committee_service.rs,
    preparation_service.rs)."""
    bls.set_backend("host")
    try:
        spec = replace(minimal_spec(), altair_fork_epoch=0)
        h = BeaconChainHarness(spec, E, validator_count=8)
        vc = ValidatorClient(
            h.chain, h.keypairs, spec, E, fee_recipient=b"\xaa" * 20
        )
        for slot in range(1, 4):
            h.slot_clock.set_slot(slot)
            vc.on_slot(slot)
        # block at slot 2+ was produced from the pool, not the empty
        # aggregate: all committee members are managed, so full bits
        head_block = h.chain.head_block()
        agg = head_block.message.body.sync_aggregate
        assert any(agg.sync_committee_bits), "pool-built aggregate is empty"
        # process_sync_aggregate accepted it during import (signature
        # checked under host crypto) — the head advanced to slot 3
        assert h.chain.head_state.slot == 3
        # preparation reached the chain
        assert h.chain.proposer_preparations
        assert set(h.chain.proposer_preparations.values()) == {b"\xaa" * 20}
    finally:
        bls.set_backend("fake_crypto")


def test_vc_aggregation_duties():
    """Selected aggregators publish SignedAggregateAndProofs built from
    the pool's best aggregate; the chain verifies all three signatures
    (selection proof, aggregator, attestation) under real crypto."""
    bls.set_backend("host")
    try:
        spec = replace(minimal_spec(), altair_fork_epoch=0)
        h = BeaconChainHarness(spec, E, validator_count=8)
        vc = ValidatorClient(h.chain, h.keypairs, spec, E)
        published = []
        for slot in range(1, 5):
            h.slot_clock.set_slot(slot)
            vc.block_service.propose_if_due(slot)
            head = h.chain.head_root
            vc.attestation_service.attest(slot, head)
            published += vc.attestation_service.aggregate_if_selected(slot)
        # minimal-spec TARGET_AGGREGATORS_PER_COMMITTEE makes selection
        # near-certain with these committee sizes; require at least one
        assert published, "no aggregator selected across 4 slots"
        agg = published[0]
        assert sum(agg.message.aggregate.aggregation_bits) >= 1
        # the chain accepted it into the observed-aggregators dedup
        data = agg.message.aggregate.data
        assert h.chain.observed_aggregators.is_known(
            data.target.epoch, agg.message.aggregator_index
        )
    finally:
        bls.set_backend("fake_crypto")


def _slot_aggregates(monkeypatch, n_min=3):
    """One slot's SignedAggregateAndProofs from a real-crypto VC, captured
    before the chain sees them."""
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    # 32 validators: one 4-member committee per slot, every member selected
    h = BeaconChainHarness(spec, E, validator_count=32)
    vc = ValidatorClient(h.chain, h.keypairs, spec, E)
    captured: list = []
    monkeypatch.setattr(
        vc.node, "publish_aggregates", lambda aggs: captured.extend(aggs) or []
    )
    for slot in range(1, 5):
        h.slot_clock.set_slot(slot)
        vc.block_service.propose_if_due(slot)
        vc.attestation_service.attest(slot, h.chain.head_root)
        captured.clear()
        vc.attestation_service.aggregate_if_selected(slot)
        if len(captured) >= n_min:
            return h, list(captured)
    pytest.skip("no slot with enough selected aggregators")


@pytest.mark.parametrize(
    "tamper", [None, "bad_signature", "duplicate", "duplicate_after_bad"]
)
def test_aggregate_batch_is_one_signature_batch(monkeypatch, tamper):
    """The slot's aggregates verify as ONE signature batch; a bad or
    duplicated aggregate is rejected alone, the rest still apply — as if
    they were verified one after another."""
    bls.set_backend("host")
    try:
        h, aggs = _slot_aggregates(monkeypatch)
        if tamper == "bad_signature":
            # a well-formed signature over the wrong message
            aggs[0].signature = aggs[0].message.selection_proof
        elif tamper == "duplicate":
            aggs.append(aggs[0])
        elif tamper == "duplicate_after_bad":
            # the aggregator's first aggregate fails its signature check, so
            # its second (valid) one is not a duplicate
            aggs.append(aggs[0].copy())
            aggs[0].signature = aggs[0].message.selection_proof
        calls = []
        verify = bls.verify_signature_sets
        monkeypatch.setattr(
            bls,
            "verify_signature_sets",
            lambda sets, rng=None: calls.append(len(sets)) or verify(sets, rng),
        )
        results = h.chain.process_aggregate_batch(aggs)
        rejected = [i for i, r in enumerate(results) if isinstance(r, Exception)]
        if tamper is None:
            assert rejected == [] and calls == [3 * len(aggs)]
        elif tamper == "bad_signature":
            assert rejected == [0]
            assert calls[0] == 3 * len(aggs) and calls[1:] == [3] * len(aggs)
        elif tamper == "duplicate":
            assert rejected == [len(aggs) - 1] and calls == [3 * len(aggs)]
            assert "already seen" in str(results[-1])
        else:
            assert rejected == [0]
            # the batch fails; the first aggregate is checked alone, then
            # every other one, its valid twin included
            assert calls == [3 * len(aggs)] + [3] * len(aggs)
        for i, agg in enumerate(aggs):
            data = agg.message.aggregate.data
            known = h.chain.observed_aggregators.is_known(
                data.target.epoch, agg.message.aggregator_index
            )
            assert known == (i not in rejected or tamper != "bad_signature")
    finally:
        bls.set_backend("fake_crypto")


def test_sync_message_rejects_non_member_and_bad_signature():
    from lighthouse_tpu.beacon_chain.sync_pool import SyncMessageError

    bls.set_backend("host")
    try:
        spec = replace(minimal_spec(), altair_fork_epoch=0)
        h = BeaconChainHarness(spec, E, validator_count=8)
        t = h.chain.types
        # bad signature for a real member
        state = h.chain.head_state
        member_pk = bytes(state.current_sync_committee.pubkeys[0])
        vi = next(
            i for i, v in enumerate(state.validators)
            if bytes(v.pubkey) == member_pk
        )
        msg = t.SyncCommitteeMessage(
            slot=0,
            beacon_block_root=h.chain.head_root,
            validator_index=vi,
            signature=b"\x01" * 96,
        )
        with pytest.raises(SyncMessageError, match="signature"):
            h.chain.process_sync_committee_message(msg)
        with pytest.raises(SyncMessageError, match="unknown validator"):
            h.chain.process_sync_committee_message(
                t.SyncCommitteeMessage(
                    slot=0,
                    beacon_block_root=h.chain.head_root,
                    validator_index=10_000,
                    signature=b"\x01" * 96,
                )
            )
    finally:
        bls.set_backend("fake_crypto")


def test_vc_refuses_repeat_slot_proposal():
    h, vc = _vc_setup(validator_count=8)
    h.slot_clock.set_slot(1)
    root = vc.on_slot(1)
    assert root is not None
    # re-running the same slot: block may be rebuilt with a different
    # state (e.g. new attestations) — slashing protection must refuse a
    # conflicting second signature rather than double-sign
    import lighthouse_tpu.validator_client as V

    from lighthouse_tpu.types.containers import build_types

    head = h.chain.head_block()
    pubkey = h.keypairs[head.message.proposer_index].pk.to_bytes()
    t = build_types(E)
    tf = t.types_for_fork(t.fork_of_block(head.message))
    conflicting = tf.BeaconBlock(
        slot=1,
        proposer_index=head.message.proposer_index,
        parent_root=head.message.parent_root,
        state_root=b"\x42" * 32,  # differs from the signed block
        body=tf.BeaconBlockBody(),
    )
    with pytest.raises(NotSafe):
        vc.store.sign_block(pubkey, conflicting, h.chain.head_state, vc.spec, E)


def test_doppelganger_gates_signing():
    h, vc = _vc_setup(validator_count=8)
    vc.doppelganger.begin(current_epoch=0)
    h.slot_clock.set_slot(1)
    assert vc.on_slot(1) is None  # gated
    later_slot = 2 * E.SLOTS_PER_EPOCH + 1
    h.slot_clock.set_slot(later_slot)
    assert vc.doppelganger.signing_enabled(2)


def test_keymanager_api_lifecycle():
    """VC keymanager HTTP API (validator_client/src/http_api): bearer
    auth, list/import/delete keystores with interchange export, fee
    recipient get/set feeding the preparation service."""
    import json as _json
    import urllib.request
    from urllib.error import HTTPError

    from lighthouse_tpu.crypto.keystore import Keystore
    from lighthouse_tpu.validator_client.http_api import KeymanagerServer

    h, vc = _vc_setup(validator_count=4)
    srv = KeymanagerServer(vc).start()
    base = f"http://127.0.0.1:{srv.port}"

    def call(method, path, body=None, token=srv.token):
        req = urllib.request.Request(
            f"{base}{path}",
            data=_json.dumps(body).encode() if body is not None else None,
            method=method,
            headers={
                "Authorization": f"Bearer {token}",
                "Content-Type": "application/json",
            },
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, _json.loads(r.read())

    try:
        # auth required
        try:
            call("GET", "/eth/v1/keystores", token="wrong")
            raise AssertionError("unauthenticated request accepted")
        except HTTPError as e:
            assert e.code == 401

        _code, listed = call("GET", "/eth/v1/keystores")
        assert len(listed["data"]) == 4

        # import a 5th key
        kp5 = bls.interop_keypairs(6)[5]
        ks = Keystore.encrypt(
            kp5.sk.scalar.to_bytes(32, "big"), "pw",
            pubkey=kp5.pk.to_bytes(), _fast_kdf=True,
        )
        _code, res = call(
            "POST", "/eth/v1/keystores",
            {"keystores": [ks.to_json()], "passwords": ["pw"]},
        )
        assert res["data"] == [{"status": "imported"}]
        assert len(call("GET", "/eth/v1/keystores")[1]["data"]) == 5
        # duplicate import reports duplicate
        _code, res = call(
            "POST", "/eth/v1/keystores",
            {"keystores": [ks.to_json()], "passwords": ["pw"]},
        )
        assert res["data"] == [{"status": "duplicate"}]

        # fee recipient set/get drives the preparation service
        pk_hex = "0x" + kp5.pk.to_bytes().hex()
        code, _ = call(
            "POST", f"/eth/v1/validator/{pk_hex}/feerecipient",
            {"ethaddress": "0x" + "ee" * 20},
        )
        assert code == 202
        _code, fr = call("GET", f"/eth/v1/validator/{pk_hex}/feerecipient")
        assert fr["data"]["ethaddress"] == "0x" + "ee" * 20

        # delete exports slashing protection
        _code, res = call("DELETE", "/eth/v1/keystores", {"pubkeys": [pk_hex]})
        assert res["data"] == [{"status": "deleted"}]
        interchange = _json.loads(res["slashing_protection"])
        assert "metadata" in interchange
        assert len(call("GET", "/eth/v1/keystores")[1]["data"]) == 4
    finally:
        srv.stop()
