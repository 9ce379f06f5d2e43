"""The Beacon API serving tier (PR 14): zero-copy columnar response
assembly pinned byte-identical against the retained per-object oracles,
spec validator statuses, id/status filters + pagination boundaries,
head-keyed response caches invalidated through a real block import, the
/headers list route, and the pubkey→index map."""

import json
import urllib.request
from dataclasses import replace

import numpy as np
import pytest

from lighthouse_tpu.beacon_chain.chain import _make_persistent
from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
from lighthouse_tpu.beacon_chain.events import ServerSentEventHandler
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.http_api import ApiError, BeaconApi, HttpApiServer
from lighthouse_tpu.http_api import columnar
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.state_processing import interop_genesis_state
from lighthouse_tpu.state_processing.registry_columns import (
    registry_columns_for,
)
from lighthouse_tpu.types.chain_spec import FAR_FUTURE_EPOCH, minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

_COMPACT = {"separators": (",", ":")}


def _dump(obj) -> bytes:
    return json.dumps(obj, **_COMPACT).encode()


class _StubChain:
    """The minimum BeaconApi needs to serve state routes."""

    def __init__(self, state, spec):
        self.head_state = state
        self.head_root = b"\xab" * 32
        self._states = {self.head_root: state}
        self._blocks_by_root = {}
        self.genesis_block_root = self.head_root
        self.genesis_validators_root = bytes(state.genesis_validators_root)
        self.event_handler = ServerSentEventHandler()
        self.spec = spec
        self.E = E
        self.store = None


def _build_state(altair: bool, n: int = 16):
    bls.set_backend("fake_crypto")
    spec = minimal_spec()
    if altair:
        spec = replace(spec, altair_fork_epoch=0)
    state = interop_genesis_state(
        bls.interop_keypairs(n), 1_600_000_000, b"\x42" * 32, spec, E
    )
    _make_persistent(state)
    return state, spec


def _diversify(state):
    """Cover every spec status family (current epoch is 0)."""
    far = FAR_FUTURE_EPOCH

    def mut(i, **kw):
        v = state.validators.mutate(i)
        for k, val in kw.items():
            setattr(v, k, val)

    mut(1, exit_epoch=3, withdrawable_epoch=9)  # active_exiting
    mut(2, slashed=True, exit_epoch=3, withdrawable_epoch=9)  # active_slashed
    mut(3, activation_epoch=far, activation_eligibility_epoch=far)  # pending_initialized
    mut(4, activation_epoch=99, activation_eligibility_epoch=0)  # pending_queued
    mut(5, exit_epoch=0, withdrawable_epoch=0)  # withdrawal_possible
    mut(6, exit_epoch=0, withdrawable_epoch=0)  # withdrawal_done (bal 0)
    state.balances[6] = 0
    mut(7, exit_epoch=0, withdrawable_epoch=9)  # exited_unslashed
    mut(8, slashed=True, exit_epoch=0, withdrawable_epoch=9)  # exited_slashed


@pytest.fixture(params=["altair", "phase0"])
def stub_api(request):
    state, spec = _build_state(altair=request.param == "altair")
    _diversify(state)
    return BeaconApi(_StubChain(state, spec))


# ---------------------------------------------------------------------------
# Vectorized statuses
# ---------------------------------------------------------------------------


def test_status_codes_match_scalar_fuzz():
    rng = np.random.default_rng(5)
    m = 512
    far = np.uint64(FAR_FUTURE_EPOCH)
    picks = np.array([0, 1, 2, 5, 50, FAR_FUTURE_EPOCH], dtype=np.uint64)
    aee = picks[rng.integers(0, picks.size, m)]
    ae = picks[rng.integers(0, picks.size, m)]
    ee = picks[rng.integers(0, picks.size, m)]
    we = picks[rng.integers(0, picks.size, m)]
    slashed = rng.random(m) < 0.3
    bal = np.where(rng.random(m) < 0.2, 0, 32_000_000_000).astype(np.uint64)
    for cur in (0, 1, 3, 49, 51):
        codes = columnar.status_codes(aee, ae, ee, we, slashed, bal, cur)
        for i in range(m):
            want = columnar.validator_status(
                int(aee[i]), int(ae[i]), int(ee[i]), int(we[i]),
                bool(slashed[i]), int(bal[i]), cur,
            )
            assert columnar.STATUSES[codes[i]] == want, (i, cur)
    assert far == np.uint64(FAR_FUTURE_EPOCH)


# ---------------------------------------------------------------------------
# Byte-identical differential: columnar vs per-object oracle
# ---------------------------------------------------------------------------


def test_validators_full_table_byte_identical(stub_api):
    api = stub_api
    body, ctype = api.serve_state_validators("head")
    assert ctype == "application/json"
    ref = _dump(api.state_validators_reference(api.chain.head_state))
    assert body == ref
    # every status family is exercised by the diversified registry
    statuses = {e["status"] for e in json.loads(body)["data"]}
    assert statuses == set(columnar.STATUSES)


def test_validators_filters_byte_identical(stub_api):
    api = stub_api
    st = api.chain.head_state
    full = json.loads(_dump(api.state_validators_reference(st)))
    pk9 = "0x" + bytes(st.validators[9].pubkey).hex()
    cases = [
        {"id": ["0", "9", "3"]},
        {"id": [pk9, "2"]},
        {"status": ["active"]},
        {"status": ["exited_slashed", "pending"]},
        {"limit": "5"},
        {"limit": "4", "offset": "7"},
        {"status": ["active"], "limit": "2", "offset": "1"},
    ]
    for query in cases:
        body, _ = api.serve_state_validators("head", query)
        doc = json.loads(body)
        # expected: filter the oracle's full table the spec way
        rows = full["data"]
        if "id" in query:
            wanted = set()
            for v in query["id"]:
                if v.isdigit():
                    wanted.add(int(v))
                else:
                    wanted.add(9)  # pk9 is the only pubkey used
            rows = [r for r in rows if int(r["index"]) in wanted]
        if "status" in query:
            keep = set()
            for s in query["status"]:
                if s in columnar.STATUS_FAMILIES:
                    keep.update(
                        columnar.STATUSES[c]
                        for c in columnar.STATUS_FAMILIES[s]
                    )
                else:
                    keep.add(s)
            rows = [r for r in rows if r["status"] in keep]
        off = int(query.get("offset", 0))
        lim = query.get("limit")
        rows = rows[off : off + int(lim)] if lim is not None else rows[off:]
        expected = dict(full, data=rows)
        assert doc == expected, query
        # byte-identity against the oracle rendering of the same rows
        assert body == _dump(expected), query


def test_balances_json_and_ssz(stub_api):
    api = stub_api
    st = api.chain.head_state
    body, _ = api.serve_state_validator_balances("head")
    assert body == _dump(api.state_validator_balances_reference(st))
    ssz, ctype = api.serve_state_validator_balances("head", ssz=True)
    assert ctype == "application/octet-stream"
    n = len(st.balances)
    expected = b"".join(
        i.to_bytes(8, "little") + int(st.balances[i]).to_bytes(8, "little")
        for i in range(n)
    )
    assert ssz == expected
    # paginated SSZ slice
    ssz_page, _ = api.serve_state_validator_balances(
        "head", {"limit": "3", "offset": "2"}, ssz=True
    )
    assert ssz_page == expected[2 * 16 : 5 * 16]


def test_committees_byte_identical(stub_api):
    api = stub_api
    body, _ = api.serve_state_committees("head")
    assert body == _dump(api.state_committees("head"))


def test_pagination_boundaries(stub_api):
    api = stub_api
    n = len(api.chain.head_state.balances)
    for query, want in (
        ({"limit": "0"}, 0),
        ({"offset": str(n)}, 0),
        ({"offset": str(n + 50)}, 0),
        ({"limit": str(n * 2)}, n),
        ({"limit": "5", "offset": str(n - 2)}, 2),
    ):
        body, _ = api.serve_state_validators("head", query)
        assert len(json.loads(body)["data"]) == want, query
    for bad in (
        {"limit": "-1"},
        {"limit": "nope"},
        {"offset": "-3"},
        {"status": ["bogus_status"]},
        {"id": ["0xzz"]},
    ):
        with pytest.raises(ApiError) as ei:
            api.serve_state_validators("head", bad)
        assert ei.value.code == 400, bad


def test_id_filter_string_ids_regression(stub_api):
    """The seed compared int indices against the request's STRING ids
    (`i not in indices` — never matched). Mixed string/pubkey ids must
    resolve, out-of-range and unknown ones drop silently."""
    api = stub_api
    st = api.chain.head_state
    pk = "0x" + bytes(st.validators[4].pubkey).hex()
    unknown_pk = "0x" + "77" * 48
    body, _ = api.serve_state_validators(
        "head", {"id": ["3", pk, "999999", unknown_pk]}
    )
    got = [e["index"] for e in json.loads(body)["data"]]
    assert got == ["3", "4"]
    # the oracle entry normalizes the same way
    doc = api.state_validators("head", ["3", pk, "999999", unknown_pk])
    assert [e["index"] for e in doc["data"]] == ["3", "4"]


def test_status_filter_on_oracle_path():
    """A status= filter must work (not 500) when the state has no
    resident columns — the per-object fallback computes codes too."""
    state, spec = _build_state(altair=True)
    _diversify(state)
    # a plain-list registry: no resident columns, the per-object oracle
    oracle = state.copy()
    oracle.validators = list(state.validators)
    assert registry_columns_for(oracle) is None
    api = BeaconApi(_StubChain(oracle, spec))
    body, _ = api.serve_state_validators("head", {"status": ["exited_slashed"]})
    assert [e["index"] for e in json.loads(body)["data"]] == ["8"]
    # and the oracle fallback body is the same bytes the columnar path
    # produces for the same filter
    api2 = BeaconApi(_StubChain(state, spec))
    body2, _ = api2.serve_state_validators(
        "head", {"status": ["exited_slashed"]}
    )
    assert registry_columns_for(state) is not None
    assert body2 == body


def test_block_index_survives_balanced_prune_and_import(stub_api):
    """A prune balanced by an equal number of imports (hot-map length
    unchanged) must still drop the pruned root and index the new one."""
    from lighthouse_tpu.http_api.block_index import BlockHeaderIndex

    class _Blk:
        def __init__(self, slot, parent):
            import types as _t

            body = _t.SimpleNamespace(hash_tree_root=lambda: b"\x0b" * 32)
            self.message = _t.SimpleNamespace(
                slot=slot, proposer_index=0, parent_root=parent,
                state_root=b"\x05" * 32, body=body,
            )
            self.signature = b"\x0c" * 96

    chain = stub_api.chain
    chain._blocks_by_root = {
        b"\x01" * 32: _Blk(7, b"\x00" * 32),
        b"\x02" * 32: _Blk(8, b"\x01" * 32),
    }
    index = BlockHeaderIndex(chain)
    assert index.roots_at_slot(7) == [b"\x01" * 32]
    # prune one, import one: same dict length
    del chain._blocks_by_root[b"\x01" * 32]
    chain._blocks_by_root[b"\x03" * 32] = _Blk(9, b"\x02" * 32)
    assert index.roots_at_slot(7) == []  # pruned root gone
    assert index.roots_at_slot(9) == [b"\x03" * 32]  # new root indexed
    assert index.roots_by_parent(b"\x02" * 32) == [b"\x03" * 32]


def test_server_stop_detaches_listeners():
    state, spec = _build_state(altair=True)
    chain = _StubChain(state, spec)
    api = BeaconApi(chain)
    assert len(chain.event_handler._listeners) == 2
    api.close()
    assert chain.event_handler._listeners == []


def test_columnar_assembly_counted_oracle_not(stub_api):
    api = stub_api
    c = REGISTRY.counter("api_columnar_assembly_total")
    before = c.value(route="validators")
    api.response_cache.clear()
    api.serve_state_validators("head")
    assert c.value(route="validators") == before + 1
    api.state_validators_reference(api.chain.head_state)
    assert c.value(route="validators") == before + 1  # oracle never counts


# ---------------------------------------------------------------------------
# Single validator + pubkey→index map
# ---------------------------------------------------------------------------


def test_single_validator_real_status_and_map(stub_api):
    api = stub_api
    doc = api.state_validator("head", "8")
    assert doc["data"]["status"] == "exited_slashed"
    pk = doc["data"]["validator"]["pubkey"]
    by_pk = api.state_validator("head", pk)
    assert by_pk["data"]["index"] == "8"
    assert by_pk == doc
    with pytest.raises(ApiError) as ei:
        api.state_validator("head", "0x" + "99" * 48)
    assert ei.value.code == 404
    with pytest.raises(ApiError) as ei:
        api.state_validator("head", "0x1234")
    assert ei.value.code == 400


def test_pubkey_index_first_occurrence_and_growth():
    state, spec = _build_state(altair=True, n=8)
    cols = registry_columns_for(state)
    cols.refresh(state)
    # duplicate pubkey: index must resolve to the FIRST occurrence
    v = state.validators.mutate(5)
    v.pubkey = bytes(state.validators[2].pubkey)
    cols.refresh(state)
    assert cols.pubkey_index()[bytes(state.validators[2].pubkey)] == 2
    # growth invalidates: an appended validator becomes findable
    new = state.validators[0].copy()
    new.pubkey = b"\x31" * 48
    state.validators.append(new)
    cols.refresh(state)
    assert cols.pubkey_index()[b"\x31" * 48] == len(state.validators) - 1


# ---------------------------------------------------------------------------
# Response cache: hit/miss, head-change invalidation via real import
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rig():
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=16)
    h.extend_chain(E.SLOTS_PER_EPOCH + 2)
    server = HttpApiServer(h.chain).start()
    yield h, server
    server.stop()


def _get(server, path, accept=None):
    req = urllib.request.Request(f"http://127.0.0.1:{server.port}{path}")
    if accept:
        req.add_header("Accept", accept)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            data = resp.read()
            ctype = resp.headers.get("Content-Type", "")
            return resp.status, (json.loads(data) if "json" in ctype else data)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_cache_hit_and_head_change_invalidation(rig):
    h, server = rig
    api = server.api
    hits = REGISTRY.counter("api_cache_hits_total")
    misses = REGISTRY.counter("api_cache_misses_total")
    evictions = REGISTRY.counter("api_cache_evictions_total")
    api.response_cache.clear()
    h0, m0, e0 = (
        hits.value(route="validators"),
        misses.value(route="validators"),
        evictions.value(route="validators"),
    )
    _, first = _get(server, "/eth/v1/beacon/states/head/validators")
    assert misses.value(route="validators") == m0 + 1
    _, second = _get(server, "/eth/v1/beacon/states/head/validators")
    assert hits.value(route="validators") == h0 + 1
    assert first == second
    assert len(api.response_cache) >= 1
    # a REAL block import moves the head; the head event (the same one
    # the SSE stream consumes) evicts entries keyed to the old head
    h.extend_chain(1)
    assert evictions.value(route="validators") >= e0 + 1
    _, third = _get(server, "/eth/v1/beacon/states/head/validators")
    assert misses.value(route="validators") == m0 + 2
    # and the fresh body is byte-identical to the oracle on the NEW head
    body, _ = api.serve_state_validators("head")
    assert body == _dump(api.state_validators_reference(h.chain.head_state))


def test_cache_byte_budget_lru():
    from lighthouse_tpu.http_api.response_cache import ResponseCache

    cache = ResponseCache(max_bytes=100)
    cache.put("validators", b"\x01" * 32, "a", b"x" * 40, "application/json")
    cache.put("validators", b"\x01" * 32, "b", b"y" * 40, "application/json")
    assert len(cache) == 2
    cache.put("validators", b"\x01" * 32, "c", b"z" * 40, "application/json")
    assert len(cache) == 2  # oldest evicted
    assert cache.get("validators", b"\x01" * 32, "a") is None
    assert cache.get("validators", b"\x01" * 32, "c") is not None
    # an over-budget body is served uncached, not stored
    cache.put("validators", b"\x01" * 32, "big", b"w" * 200, "application/json")
    assert cache.get("validators", b"\x01" * 32, "big") is None


def test_cache_generation_guard():
    """A body built before a concurrent invalidation must not be
    re-cached as fresh (the /headers block-event race)."""
    from lighthouse_tpu.http_api.response_cache import ResponseCache

    cache = ResponseCache(max_bytes=1000)
    gen = cache.generation
    cache.evict_route("headers")  # the race: invalidation mid-build
    cache.put("headers", b"\x01" * 32, "q", b"stale", "application/json",
              if_generation=gen)
    assert cache.get("headers", b"\x01" * 32, "q") is None
    cache.put("headers", b"\x01" * 32, "q", b"fresh", "application/json",
              if_generation=cache.generation)
    assert cache.get("headers", b"\x01" * 32, "q")[0] == b"fresh"


def test_trace_stages_recorded(rig):
    _h, server = rig
    server.api.response_cache.clear()
    deltas = {}
    for name in ("cache_lookup", "assemble", "serialize"):
        deltas[name] = REGISTRY.histogram(f"trace_span_seconds_{name}").count
    _get(server, "/eth/v1/beacon/states/head/validators")
    for name in ("cache_lookup", "assemble", "serialize"):
        assert (
            REGISTRY.histogram(f"trace_span_seconds_{name}").count
            > deltas[name]
        ), name


def test_balances_ssz_over_http(rig):
    h, server = rig
    status, raw = _get(
        server,
        "/eth/v1/beacon/states/head/validator_balances",
        accept="application/octet-stream",
    )
    assert status == 200
    st = h.chain.head_state
    assert len(raw) == len(st.balances) * 16
    assert int.from_bytes(raw[8:16], "little") == int(st.balances[0])


# ---------------------------------------------------------------------------
# /headers list + block-root-indexed lookups
# ---------------------------------------------------------------------------


def test_headers_list_route(rig):
    h, server = rig
    head = h.chain.head_block()
    head_slot = int(head.message.slot)
    _, doc = _get(server, "/eth/v1/beacon/headers")
    assert [e["root"] for e in doc["data"]] == [
        "0x" + h.chain.head_root.hex()
    ]
    assert doc["data"][0]["canonical"] is True
    # the list entry equals the single-header route's data
    _, single = _get(server, f"/eth/v1/beacon/headers/{head_slot}")
    assert doc["data"][0]["header"] == single["data"]["header"]
    # slot filter
    _, by_slot = _get(server, f"/eth/v1/beacon/headers?slot={head_slot - 1}")
    assert len(by_slot["data"]) == 1
    assert by_slot["data"][0]["header"]["message"]["slot"] == str(head_slot - 1)
    # parent_root filter finds the head by its parent
    parent = single["data"]["header"]["message"]["parent_root"]
    _, by_parent = _get(
        server, f"/eth/v1/beacon/headers?parent_root={parent}"
    )
    assert [e["root"] for e in by_parent["data"]] == [
        "0x" + h.chain.head_root.hex()
    ]
    _, bad = _get(server, "/eth/v1/beacon/headers?slot=notanum")
    assert bad["code"] == 400


def test_headers_cache_evicted_on_block_event(rig):
    h, server = rig
    evictions = REGISTRY.counter("api_cache_evictions_total")
    server.api.response_cache.clear()
    _get(server, "/eth/v1/beacon/headers")
    e0 = evictions.value(route="headers")
    h.extend_chain(1)
    assert evictions.value(route="headers") >= e0 + 1
    # the fresh listing shows the new head
    _, doc = _get(server, "/eth/v1/beacon/headers")
    assert doc["data"][0]["root"] == "0x" + h.chain.head_root.hex()


def test_block_by_root_served_from_store_after_hot_eviction(rig):
    """Pruned-from-hot blocks serve through the index's store LRU (one
    deserialization per residency, not per request)."""
    h, server = rig
    root = h.chain.head_root
    block = h.chain._blocks_by_root.pop(root)
    try:
        _, doc = _get(server, f"/eth/v1/beacon/headers/0x{root.hex()}")
        assert doc["data"]["root"] == "0x" + root.hex()
        status, ssz = _get(
            server,
            f"/eth/v2/beacon/blocks/0x{root.hex()}",
            accept="application/octet-stream",
        )
        assert status == 200 and ssz == block.serialize()
    finally:
        h.chain._blocks_by_root[root] = block
