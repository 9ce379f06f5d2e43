"""Batched VC duty pipeline (PR 19): differential oracles.

Every batch program keeps its per-key predecessor as the oracle:
fixed-base scalar mul vs the generic ladder (same group elements, same
compressed bytes), `bls.sign_batch` vs per-key `sk.sign`, the epoch duty
table vs the committee walk, the batch slashing-protection transaction
vs sequential per-key checks (including hostile surround / lowball /
double-vote mixes and crash-point atomicity), and the whole VC pipeline
batch-vs-per-key (the per-key service methods swapped in) — identical
chain roots, identical slashing-DB end state. Keymanager keystore routes are covered
at scale (satellite: 1k in tier-1, 10k behind the slow mark)."""

import random
from dataclasses import replace

import pytest

from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls12_381 import (
    FQ2,
    R,
    FixedBaseTable,
    fixed_base_window,
    fixed_base_worthwhile,
    g2_to_bytes,
    hash_to_g2,
    pt_mul,
)
from lighthouse_tpu.crypto.keystore import Keystore
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
import lighthouse_tpu.validator_client as vc_module
from lighthouse_tpu.validator_client import (
    AttestationService,
    DutiesService,
    SyncCommitteeService,
    ValidatorClient,
    _columns,
)
from lighthouse_tpu.validator_client.http_api import KeymanagerApi
from lighthouse_tpu.validator_client.slashing_protection import (
    NotSafe,
    SlashingDatabase,
)


def _vc_setup(validator_count=16):
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=validator_count)
    vc = ValidatorClient(h.chain, h.keypairs, spec, E)
    return h, vc


# --- fixed-base windowed scalar multiplication ------------------------------


def test_fixed_base_table_matches_pt_mul():
    """Differential fuzz: table lookups + adds yield the exact same
    group elements (hence identical compressed bytes) as the generic
    wNAF ladder — at edge scalars and across window widths."""
    rng = random.Random(0xF1EB)
    h = hash_to_g2(b"vc-batch-fixture")
    scalars = [0, 1, 2, 3, R - 1, (1 << 255) - 1] + [
        rng.randrange(R) for _ in range(5)
    ]
    for w in (2, 5, 10):
        tbl = FixedBaseTable(FQ2, h, w)
        for s in scalars:
            assert g2_to_bytes(tbl.mul(s)) == g2_to_bytes(pt_mul(FQ2, h, s))


def test_fixed_base_window_and_worthwhile():
    # wider windows only pay off at larger batch sizes
    assert fixed_base_window(1) <= fixed_base_window(100)
    assert fixed_base_window(100) <= fixed_base_window(100_000)
    # one signature never amortizes a table; a committee does
    assert not fixed_base_worthwhile(1)
    assert fixed_base_worthwhile(3000)


def test_fixed_base_rejects_bad_inputs():
    h = hash_to_g2(b"vc-batch-bad-inputs")
    with pytest.raises(ValueError):
        FixedBaseTable(FQ2, h, 1)
    tbl = FixedBaseTable(FQ2, h, 3)
    with pytest.raises(ValueError):
        tbl.mul(-1)


# --- batch signing ----------------------------------------------------------


def test_sign_batch_bit_identical_host(monkeypatch):
    """Host backend: sign_batch output is BIT-identical to per-key
    signing, on both scalar-mul strategies (generic ladder for small
    groups, fixed-base window table when forced worthwhile)."""
    bls.set_backend("host")
    try:
        kps = bls.interop_keypairs(6)
        sks = [kp.sk for kp in kps]
        msgs = [b"\x01" * 32] * 3 + [b"\x02" * 32] * 2 + [b"\x03" * 32]
        per_key = [sk.sign(m).to_bytes() for sk, m in zip(sks, msgs)]
        for force_fixed_base in (False, True):
            if force_fixed_base:
                monkeypatch.setattr(
                    bls, "fixed_base_worthwhile", lambda m: True
                )
            batch = bls.sign_batch(sks, msgs)
            assert [s.to_bytes() for s in batch] == per_key
    finally:
        bls.set_backend("fake_crypto")


def test_sign_batch_fake_backend_and_length_mismatch():
    bls.set_backend("fake_crypto")
    kps = bls.interop_keypairs(4)
    msgs = [b"\x05" * 32] * 4
    batch = bls.sign_batch([k.sk for k in kps], msgs)
    assert [s.to_bytes() for s in batch] == [
        k.sk.sign(m).to_bytes() for k, m in zip(kps, msgs)
    ]
    with pytest.raises(bls.BlsError):
        bls.sign_batch([kps[0].sk], [])


# --- epoch duty table -------------------------------------------------------


def test_epoch_duty_table_matches_committee_walk():
    from lighthouse_tpu.state_processing.accessors import (
        committee_cache_at,
        compute_start_slot_at_epoch,
        epoch_duty_table,
    )

    h, _vc = _vc_setup(validator_count=24)
    st = h.chain.head_state
    table = epoch_duty_table(st, 0, E)
    cc = committee_cache_at(st, 0, E)
    start = compute_start_slot_at_epoch(0, E)
    expected = {}
    for slot in range(start, start + E.SLOTS_PER_EPOCH):
        for ci in range(cc.committees_per_slot):
            committee = cc.committee(slot, ci)
            for pos, vi in enumerate(committee):
                expected[int(vi)] = (slot, ci, pos, len(committee))
    idx = list(range(-2, len(st.validators) + 2))
    found, slots, cidx, pos, size = table.lookup(idx)
    hits = [i for i, f in zip(idx, found) if f]
    got = {
        vi: (int(s), int(c), int(p), int(n))
        for vi, s, c, p, n in zip(hits, slots, cidx, pos, size)
    }
    assert got == expected
    # negative and beyond-registry indices report not-found
    assert not found[0] and not found[1] and not found[-1]


# --- duties service ---------------------------------------------------------


def test_our_indices_pubkey_index_matches_scan():
    """Satellite: `_our_indices` resolves through the resident columns'
    pubkey_index(); column-less states keep the O(n) scan."""
    h, vc = _vc_setup()
    st = h.chain.head_state
    ds = vc.duties_service
    assert _columns(st) is not None  # the fast path is actually live
    via_columns = ds._our_indices(st)
    assert via_columns == ds._our_indices_scan(st)
    assert sorted(via_columns) == list(range(16))
    # column-less fallback: a plain-list registry must not change results
    plain = st.copy()
    plain.validators = list(st.validators)
    assert _columns(plain) is None
    assert ds._our_indices(plain) == via_columns


def test_duties_bulk_fetch_matches_scan(monkeypatch):
    h, vc = _vc_setup()
    ds = vc.duties_service
    bulk = ds.attester_duties(0)
    st = vc.node.head_state()
    scan = ds._attester_duties_scan(st, 0, ds._our_indices(st))
    assert bulk == scan
    # pagination must not change the result set or its order
    monkeypatch.setenv("LIGHTHOUSE_TPU_VC_DUTIES_PAGE", "3")
    ds._duty_cache.clear()
    assert ds.attester_duties(0) == scan


def test_http_duties_route_matches_vc_bulk_fetch():
    """The Beacon API duties route and the in-process bulk surface
    resolve through the same epoch duty table — identical assignments."""
    from lighthouse_tpu.http_api import BeaconApi

    h, vc = _vc_setup()
    api = BeaconApi(h.chain)
    rows = api.attester_duties(0, list(range(16)))["data"]
    local = vc.node.attester_duties(0, list(range(16)))
    local.sort(
        key=lambda d: (d.slot, d.committee_index, d.committee_position)
    )
    assert [
        (
            int(r["validator_index"]),
            int(r["slot"]),
            int(r["committee_index"]),
            int(r["validator_committee_index"]),
            int(r["committee_length"]),
        )
        for r in rows
    ] == [
        (
            d.validator_index,
            d.slot,
            d.committee_index,
            d.committee_position,
            d.committee_size,
        )
        for d in local
    ]


# --- whole-pipeline differential -------------------------------------------


def _route_vc_to_per_key(monkeypatch):
    """Point the VC's public service methods at the retained per-key
    oracle methods, and its registry lookups at the O(n) scans."""
    monkeypatch.setattr(vc_module, "_columns", lambda state: None)
    monkeypatch.setattr(
        DutiesService, "_attester_duties_bulk", lambda self, epoch, ours: None
    )
    monkeypatch.setattr(AttestationService, "attest", AttestationService._attest_per_key)
    monkeypatch.setattr(
        AttestationService,
        "aggregate_if_selected",
        AttestationService._aggregate_per_key,
    )
    monkeypatch.setattr(
        SyncCommitteeService,
        "sign_messages",
        SyncCommitteeService._sign_messages_per_key,
    )


def test_vc_batch_pipeline_matches_per_key_oracle(monkeypatch):
    """Tentpole oracle: drive two identical chains for 2 epochs, one VC
    on the batch pipeline and one on the per-key oracle methods.
    Chain head roots (covering every published block / attestation /
    sync message bit-for-bit), finality, and the slashing-DB end state
    must be identical."""
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    results = {}
    for mode in ("batch", "per_key"):
        if mode == "per_key":
            _route_vc_to_per_key(monkeypatch)
        bls.set_backend("fake_crypto")
        h = BeaconChainHarness(spec, E, validator_count=16)
        vc = ValidatorClient(h.chain, h.keypairs, spec, E)
        roots = []
        for slot in range(1, 2 * E.SLOTS_PER_EPOCH + 1):
            h.slot_clock.set_slot(slot)
            vc.on_slot(slot)
            roots.append(bytes(h.chain.head_root))
        db = vc.store.slashing_db._conn
        dump = (
            db.execute(
                "SELECT validator_id, slot, signing_root FROM signed_blocks"
                " ORDER BY validator_id, slot"
            ).fetchall(),
            db.execute(
                "SELECT validator_id, source_epoch, target_epoch,"
                " signing_root FROM signed_attestations"
                " ORDER BY validator_id, target_epoch"
            ).fetchall(),
        )
        results[mode] = (roots, dump, h.finalized_epoch)
    assert results["batch"] == results["per_key"]


def test_vc_duty_cycle_trace_root_recorded():
    from lighthouse_tpu.metrics import REGISTRY

    h, vc = _vc_setup(validator_count=8)

    def _traces():
        for line in REGISTRY.expose().splitlines():
            if line.startswith("trace_collector_traces_total") and (
                'root="vc_duty_cycle"' in line
            ):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    before = _traces()
    h.slot_clock.set_slot(1)
    vc.on_slot(1)
    assert _traces() > before


# --- batched slashing protection -------------------------------------------


def test_slashing_batch_matches_sequential_hostile_fuzz():
    """Hostile mix — lowball targets, surrounds, surrounded-by, double
    votes, idempotent re-signs, source>target, unregistered keys — the
    batch's per-entry refusals and the DB end state equal sequential
    per-key calls in entry order."""
    rng = random.Random(0x5EED)
    pks = [bytes([i + 1]) * 48 for i in range(8)]
    db_seq, db_batch = SlashingDatabase(), SlashingDatabase()
    for db in (db_seq, db_batch):
        for pk in pks[:7]:  # pks[7] stays unregistered
            db.register_validator(pk)
    for _round in range(6):
        entries = []
        for _ in range(25):
            entries.append(
                (
                    rng.choice(pks),
                    rng.randrange(0, 14),  # sometimes > target
                    rng.randrange(0, 12),
                    bytes([rng.randrange(4)]) * 32,  # forced collisions
                )
            )
        seq_statuses = []
        for pk, s, t, root in entries:
            try:
                db_seq.check_and_insert_attestation(pk, s, t, root)
                seq_statuses.append(None)
            except NotSafe as e:
                seq_statuses.append(str(e))
        batch_statuses = [
            None if st is None else str(st)
            for st in db_batch.check_and_insert_attestations_batch(entries)
        ]
        assert batch_statuses == seq_statuses
    q = (
        "SELECT validator_id, source_epoch, target_epoch, signing_root"
        " FROM signed_attestations ORDER BY validator_id, target_epoch"
    )
    assert (
        db_seq._conn.execute(q).fetchall()
        == db_batch._conn.execute(q).fetchall()
    )


def test_slashing_batch_atomic_on_crash(monkeypatch):
    """Satellite: an interrupted batch leaves the DB at the pre-batch
    watermark — even when the crash lands AFTER part of the batch was
    staged into sqlite."""
    db = SlashingDatabase()
    pk = b"\xaa" * 48
    db.register_validator(pk)
    db.check_and_insert_attestation(pk, 0, 1, b"\x01" * 32)
    q = "SELECT * FROM signed_attestations ORDER BY target_epoch"
    before = db._conn.execute(q).fetchall()

    real = SlashingDatabase._insert_attestation_rows

    def crash_after_partial_stage(rows):
        real(db, rows[:1])  # first row staged, then the process "dies"
        raise RuntimeError("crash mid-batch")

    monkeypatch.setattr(db, "_insert_attestation_rows", crash_after_partial_stage)
    with pytest.raises(RuntimeError, match="crash mid-batch"):
        db.check_and_insert_attestations_batch(
            [(pk, 1, 2, b"\x02" * 32), (pk, 2, 3, b"\x03" * 32)]
        )
    assert db._conn.execute(q).fetchall() == before
    # the rolled-back entries are still signable afterwards
    monkeypatch.setattr(db, "_insert_attestation_rows", lambda rows: real(db, rows))
    assert db.check_and_insert_attestations_batch(
        [(pk, 1, 2, b"\x02" * 32)]
    ) == [None]


def test_slashing_batch_refuses_only_slashable_entry():
    """Satellite: one slashable message in a batch refuses ONLY that
    message; the rest commit."""
    db = SlashingDatabase()
    pks = [bytes([i + 1]) * 48 for i in range(3)]
    for pk in pks:
        db.register_validator(pk)
        db.check_and_insert_attestation(pk, 2, 3, b"\x0a" * 32)
    statuses = db.check_and_insert_attestations_batch(
        [
            (pks[0], 3, 4, b"\x0b" * 32),  # fine
            (pks[1], 1, 5, b"\x0c" * 32),  # surrounds the (2, 3) vote
            (pks[2], 3, 4, b"\x0d" * 32),  # fine
        ]
    )
    assert statuses[0] is None and statuses[2] is None
    assert isinstance(statuses[1], NotSafe)
    assert "surrounds" in str(statuses[1])
    n = db._conn.execute(
        "SELECT COUNT(*) FROM signed_attestations"
    ).fetchone()[0]
    assert n == 5  # 3 seed rows + the 2 safe entries


# --- keymanager keystore routes at scale ------------------------------------


def _keystore_roundtrip(n: int):
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    vc = ValidatorClient(None, [], spec, E)
    api = KeymanagerApi(vc)
    keystores, passwords, pks = [], [], []
    for i in range(n):
        sk = bls.SecretKey(i + 1)
        pk = bytes(sk.public_key().to_bytes())
        ks = Keystore.encrypt(
            (i + 1).to_bytes(32, "big"), f"pw{i}", pubkey=pk, _fast_kdf=True
        )
        keystores.append(ks.to_json())
        passwords.append(f"pw{i}")
        pks.append(pk)
    out = api.import_keystores(keystores, passwords)
    assert [s["status"] for s in out["data"]] == ["imported"] * n
    assert len(api.list_keystores()["data"]) == n
    # duplicate-add idempotence: re-import reports duplicate, count holds
    again = api.import_keystores(keystores[: min(n, 16)], passwords[: min(n, 16)])
    assert [s["status"] for s in again["data"]] == ["duplicate"] * min(n, 16)
    assert len(api.list_keystores()["data"]) == n
    # full removal round-trip
    out = api.delete_keystores(["0x" + pk.hex() for pk in pks])
    assert [s["status"] for s in out["data"]] == ["deleted"] * n
    assert api.list_keystores()["data"] == []


def test_keymanager_keystore_roundtrip_1k():
    _keystore_roundtrip(1000)


@pytest.mark.slow
def test_keymanager_keystore_roundtrip_10k():
    _keystore_roundtrip(10_000)


def test_keymanager_sign_valid_after_remove_readd():
    """Host crypto: a key removed and re-imported signs the same bytes,
    and the signature still verifies."""
    bls.set_backend("host")
    try:
        kps = bls.interop_keypairs(2)
        spec = replace(minimal_spec(), altair_fork_epoch=0)
        vc = ValidatorClient(None, kps, spec, E)
        api = KeymanagerApi(vc)
        kp = kps[0]
        pk = bytes(kp.pk.to_bytes())
        root = b"\x11" * 32
        sig_before = vc.store.signer_for(pk).sign(root)
        ks = Keystore.encrypt(
            kp.sk.to_bytes(), "pw", pubkey=pk, _fast_kdf=True
        )
        out = api.delete_keystores(["0x" + pk.hex()])
        assert out["data"][0]["status"] == "deleted"
        assert vc.store.signer_for(pk) is None
        out = api.import_keystores([ks.to_json()], ["pw"])
        assert out["data"][0]["status"] == "imported"
        sig_after = vc.store.signer_for(pk).sign(root)
        assert sig_after == sig_before
        assert bls.Signature.from_bytes(sig_after).verify(kp.pk, root)
    finally:
        bls.set_backend("fake_crypto")
