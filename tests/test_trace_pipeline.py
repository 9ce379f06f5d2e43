"""Observability pipeline: trace trees, slot-anchored delays, queue waits.

PR 9's acceptance suite: span trees assemble with correct parentage
(including across `copy_context` thread hops and the beacon_processor
worker hop), completed traces land in the bounded collector and export as
Chrome trace-event JSON over HTTP, the BlockTimesCache carries the full
slot-anchored milestone set and shouts (once, with a per-stage breakdown)
about late head blocks, queue observability fills per-WorkType
time-in-queue histograms from the real sync path, and the whole layer
switches OFF (`LIGHTHOUSE_TPU_TRACE_COLLECT=0`) back to the flat
per-name histogram behavior."""

import contextvars
import json
import logging
import threading
import time
import urllib.error
import urllib.request
from dataclasses import replace

import pytest

from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
from lighthouse_tpu.beacon_processor import BeaconProcessor, WorkType
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.metrics.trace_collector import (
    COLLECTOR,
    TraceCollector,
    span_count,
    stage_rollup,
    to_chrome_trace,
    trace_summary,
)
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
from lighthouse_tpu.utils.slot_clock import ManualSlotClock
from lighthouse_tpu.utils.tracing import Span, current_span, span


def _harness(slots=0, attest=False, validator_count=16):
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=validator_count)
    if slots:
        h.extend_chain(slots, attest=attest)
    return h


def _fake_trace(name: str, duration_s: float, trace_id: str) -> Span:
    """A hand-built closed root span (collector unit tests)."""
    s = Span(name)
    s.trace_id = trace_id
    s.duration_s = duration_s
    s.t0 = 0.0
    return s


# -- tree assembly -----------------------------------------------------------


def test_trace_tree_assembly_nested():
    with span("obs_test_root") as root:
        with span("stage_a"):
            with span("stage_a_inner"):
                pass
        with span("stage_b"):
            pass
    assert root.trace_id is not None
    assert [c.name for c in root.children] == ["stage_a", "stage_b"]
    assert [c.name for c in root.children[0].children] == ["stage_a_inner"]
    # every span carries the ROOT's trace id
    assert root.children[0].children[0].trace_id == root.trace_id
    assert span_count(root) == 4
    assert COLLECTOR.get(root.trace_id) is root
    # self-time: stages overlap when nested, so self-time (not duration)
    # is what sums back to the root's duration
    rollup = stage_rollup(root)
    assert set(rollup) == {"obs_test_root", "stage_a", "stage_a_inner", "stage_b"}
    total_self = sum(e["self_ms"] for e in rollup.values())
    assert total_self == pytest.approx(root.duration_s * 1000, rel=0.05, abs=0.5)


def test_trace_parentage_across_copy_context_thread():
    """The beacon_processor worker-hop contract, isolated: a thread run
    inside the submitter's copied Context attaches its spans under the
    submitting span."""

    def worker():
        assert current_span() is not None  # inherited via the Context
        with span("cross_thread_stage"):
            time.sleep(0.002)

    with span("obs_test_ctx_root") as root:
        ctx = contextvars.copy_context()
        t = threading.Thread(target=ctx.run, args=(worker,))
        t.start()
        t.join()
    assert [c.name for c in root.children] == ["cross_thread_stage"]
    child = root.children[0]
    assert child.trace_id == root.trace_id
    assert COLLECTOR.get(root.trace_id) is root


def test_trace_parentage_across_beacon_processor_hop():
    """End-to-end across the real scheduler: submit() copies the
    submitter's context, the worker runs the handler inside it, and the
    handler's spans land under the submitting span."""
    bp = BeaconProcessor(num_workers=2, name="obs-test")
    try:

        def handler(item):
            with span("worker_stage", item=item):
                pass

        with span("obs_test_submit_root") as root:
            assert bp.submit(WorkType.API_REQUEST, "x", handler)
            assert bp.drain(timeout=5.0)
        # the worker-side span attached under the submitting root
        assert "worker_stage" in [c.name for c in root.children]
        assert root.children[0].trace_id == root.trace_id
    finally:
        bp.shutdown()


# -- Chrome export golden shape ----------------------------------------------


def test_chrome_export_golden_shape():
    with span("obs_test_chrome", block="0xab") as root:
        with span("inner_stage"):
            pass
    doc = to_chrome_trace(root)
    # golden shape: the exact keys chrome://tracing / Perfetto load
    assert set(doc) == {"displayTimeUnit", "otherData", "traceEvents"}
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"] == {
        "trace_id": root.trace_id,
        "root": "obs_test_chrome",
    }
    assert len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}
        assert ev["ph"] == "X" and ev["cat"] == "span" and ev["pid"] == 0
        assert "self_time_ms" in ev["args"]
    # events sorted by ts, root first at ts=0
    ts = [ev["ts"] for ev in doc["traceEvents"]]
    assert ts == sorted(ts) and ts[0] == 0
    assert doc["traceEvents"][0]["args"]["block"] == "0xab"
    json.dumps(doc)  # must be JSON-serializable as-is


# -- collector bounds --------------------------------------------------------


def test_collector_ring_eviction():
    c = TraceCollector(ring_size=4, slowest_k=2)
    for i in range(10):
        c.record(_fake_trace("ring_root", 0.001 * (i + 1), f"ring-{i}"))
    recent = c.recent()
    assert len(recent) == 4  # ring bound holds
    assert [r.trace_id for r in recent] == ["ring-9", "ring-8", "ring-7", "ring-6"]
    # evicted-and-unreferenced ids are forgotten…
    assert c.get("ring-0") is None
    # …but reservoir-retained ones survive ring churn: the slowest two
    # are the last two recorded (durations increase monotonically)
    slowest = c.slowest("ring_root")
    assert [r.trace_id for r in slowest] == ["ring-9", "ring-8"]
    assert c.get("ring-8") is not None


def test_collector_slowest_reservoir_keeps_tail():
    c = TraceCollector(ring_size=2, slowest_k=2)
    c.record(_fake_trace("tail_root", 9.0, "slow-a"))  # slowest overall
    for i in range(6):
        c.record(_fake_trace("tail_root", 0.001, f"fast-{i}"))
    c.record(_fake_trace("tail_root", 5.0, "slow-b"))
    # the ring only remembers the last two, but the tail survives
    assert [r.trace_id for r in c.recent()] == ["slow-b", "fast-5"]
    assert [r.trace_id for r in c.slowest("tail_root")] == ["slow-a", "slow-b"]
    # the 9 s trace is long gone from the ring yet still fetchable by id
    assert c.get("slow-a") is not None
    assert trace_summary(c.get("slow-a"))["duration_ms"] == 9000.0


def test_collector_index_json_shape():
    c = TraceCollector(ring_size=8, slowest_k=2)
    c.record(_fake_trace("idx_root", 0.5, "idx-0"))
    doc = c.index_json()
    assert set(doc) == {"data"}
    assert set(doc["data"]) == {"recent", "slowest"}
    entry = doc["data"]["recent"][0]
    assert set(entry) == {"trace_id", "root", "duration_ms", "spans", "stages"}
    json.dumps(doc)


# -- off switch --------------------------------------------------------------


def test_off_switch_restores_flat_behavior(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_TRACE_COLLECT", "0")
    hist = REGISTRY.histogram("trace_span_seconds_obs_test_flat")
    count_before = hist.count
    ring_before = [r.trace_id for r in COLLECTOR.recent(5)]
    with span("obs_test_flat") as root:
        with span("obs_test_flat_child") as child:
            pass
    # no tree assembly: no trace ids, no child attachment, no delivery
    assert root.trace_id is None and child.trace_id is None
    assert root.children == []
    assert [r.trace_id for r in COLLECTOR.recent(5)] == ring_before
    # the flat per-name histogram still observes every span (today's
    # behavior, exactly)
    assert hist.count == count_before + 1
    # children INHERIT the off decision; a fresh root re-reads the env
    monkeypatch.setenv("LIGHTHOUSE_TPU_TRACE_COLLECT", "1")
    with span("obs_test_flat") as root2:
        pass
    assert root2.trace_id is not None


# -- block import acceptance: trace + milestones over HTTP -------------------


def test_block_import_yields_trace_tree_and_full_milestones():
    """THE acceptance path: a block imported in the harness yields a
    retrievable ≥5-span trace tree with correct parentage at
    /lighthouse/traces/<id> (Chrome trace-event JSON), and its BlockTimes
    entry carries the full slot-anchored milestone set."""
    from lighthouse_tpu.http_api import HttpApiServer
    from lighthouse_tpu.metrics.server import MetricsServer
    from lighthouse_tpu.state_processing import per_slot_processing
    from lighthouse_tpu.state_processing.accessors import (
        get_beacon_proposer_index,
    )

    h = _harness()
    # drive the gossip pipeline explicitly so EVERY milestone lands
    # (extend_chain's direct process_block skips the gossip stage)
    slot = h.chain.head_state.slot + 1
    h.slot_clock.set_slot(slot)
    h.slot_clock.set_seconds_into_slot(1.0)
    state = h.chain.head_state.copy()
    while state.slot < slot:
        per_slot_processing(state, h.spec, E)
    proposer = get_beacon_proposer_index(state, E)
    parent_root = h.chain.head_root
    block, _ = h.chain.produce_block_on_state(
        slot,
        h.randao_reveal(proposer, slot, state),
        sync_aggregate_fn=lambda st: h.make_sync_aggregate(
            st, slot, parent_root
        ),
    )
    signed = h.sign_block(block, state)
    gossip_verified = h.chain.verify_block_for_gossip(signed)
    root_hash = h.chain.process_block(gossip_verified)

    # -- the trace tree
    tree = next(t for t in COLLECTOR.recent(50) if t.name == "block_import")
    assert span_count(tree) >= 5
    child_names = {c.name for c in tree.children}
    assert {"state_transition", "fork_choice_on_block"} <= child_names
    st = next(c for c in tree.children if c.name == "state_transition")
    assert {c.name for c in st.children} >= {
        "signature_set_assembly",
        "signature_batch_verify",
    }
    for c in tree.children:
        assert c.trace_id == tree.trace_id and c.parent is tree

    # -- retrievable over HTTP as Chrome trace-event JSON, both servers
    msrv = MetricsServer().start()
    asrv = HttpApiServer(h.chain).start()
    try:
        for port in (msrv.port, asrv.port):
            doc = json.load(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/lighthouse/traces/{tree.trace_id}"
                )
            )
            assert doc["otherData"]["trace_id"] == tree.trace_id
            assert len(doc["traceEvents"]) >= 5
            idx = json.load(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/lighthouse/traces"
                )
            )
            held = [e["trace_id"] for e in idx["data"]["recent"]] + [
                e["trace_id"]
                for roots in idx["data"]["slowest"].values()
                for e in roots
            ]
            assert tree.trace_id in held
    finally:
        msrv.stop()
        asrv.stop()

    # -- the full slot-anchored milestone set
    bt = h.chain.block_times_cache.get(root_hash)
    assert bt is not None
    assert set(bt.stamps) == {
        "observed",
        "gossip_verified",
        "signature_verified",
        "payload_verified",
        "imported",
        "became_head",
    }
    assert set(bt.slot_offsets) == set(bt.stamps)
    # milestones are ordered along the pipeline
    stamps = [bt.stamps[m] for m in (
        "observed", "gossip_verified", "signature_verified",
        "payload_verified", "imported", "became_head",
    )]
    assert stamps == sorted(stamps)
    # the manual clock sat at 1.0 s into the slot for the whole import
    assert bt.slot_offsets["observed"] == pytest.approx(1.0)
    assert bt.all_delays["imported_slot_start"] == pytest.approx(1.0)
    assert "observed_to_imported" in bt.all_delays
    assert "imported_to_head" in bt.all_delays


def test_api_requests_are_traced():
    h = _harness(slots=1)
    from lighthouse_tpu.http_api import HttpApiServer

    before = REGISTRY.counter("trace_collector_traces_total").value(
        root="api_request"
    )
    srv = HttpApiServer(h.chain).start()
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/eth/v1/beacon/genesis"
        ).read()
        # the trace endpoints themselves must NOT mint api_request traces
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/lighthouse/traces"
        ).read()
    finally:
        srv.stop()
    after = REGISTRY.counter("trace_collector_traces_total").value(
        root="api_request"
    )
    assert after == before + 1


def test_trace_404_for_unknown_id():
    from lighthouse_tpu.metrics.server import MetricsServer

    srv = MetricsServer().start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/lighthouse/traces/ffffffffffff"
            )
        assert exc_info.value.code == 404
    finally:
        srv.stop()


# -- slot-anchored delays ----------------------------------------------------


def test_block_times_cache_slot_anchoring_and_breakdown():
    from lighthouse_tpu.beacon_chain.block_times_cache import BlockTimesCache

    clock = ManualSlotClock(seconds_per_slot=12)
    cache = BlockTimesCache(slot_clock=clock, seconds_per_slot=12)
    clock.set_slot(7)
    root = b"\x01" * 32

    clock.set_seconds_into_slot(2.0)
    cache.set_observed(root, 7, 100.0)
    clock.set_seconds_into_slot(3.0)
    cache.set_gossip_verified(root, 7, 100.8)
    cache.set_signature_verified(root, 7, 101.0)
    clock.set_seconds_into_slot(4.5)
    cache.set_payload_verified(root, 7, 101.5)
    cache.set_imported(root, 7, 102.0)
    cache.set_became_head(root, 7, 102.5)

    e = cache.get(root)
    assert e.slot_offsets["observed"] == pytest.approx(2.0)
    assert e.slot_offsets["gossip_verified"] == pytest.approx(3.0)
    assert e.slot_offsets["payload_verified"] == pytest.approx(4.5)
    assert e.all_delays["observed_to_imported"] == pytest.approx(2.0)
    assert e.all_delays["imported_to_head"] == pytest.approx(0.5)
    bd = e.stage_breakdown_ms()
    assert bd["gossip_verified"] == pytest.approx(800.0)
    assert bd["imported"] == pytest.approx(500.0)
    # first write wins: a replayed observation can't rewrite history
    clock.set_seconds_into_slot(9.0)
    cache.set_observed(root, 7, 999.0)
    assert e.stamps["observed"] == 100.0
    # legacy accessors still resolve (pre-milestone-chain API surface)
    assert e.observed_at == 100.0 and e.imported_at == 102.0


def test_late_head_block_warning_carries_breakdown(caplog):
    from lighthouse_tpu.beacon_chain.block_times_cache import BlockTimesCache

    clock = ManualSlotClock(seconds_per_slot=12)
    cache = BlockTimesCache(slot_clock=clock, seconds_per_slot=12)
    clock.set_slot(3)
    root = b"\x02" * 32
    cache.set_observed(root, 3, 50.0)
    cache.set_imported(root, 3, 53.4)
    clock.set_seconds_into_slot(6.0)  # way past the 4 s deadline
    with caplog.at_level(logging.WARNING, logger="lighthouse_tpu"):
        cache.set_became_head(root, 3, 53.9)
    late = [r for r in caplog.records if "late head block" in r.getMessage()]
    assert len(late) == 1
    msg = late[0].getMessage()
    assert "head_slot_offset_s=6.0" in msg
    assert "deadline_s=4.0" in msg
    assert "stage_imported_ms=3400.0" in msg  # the per-stage breakdown
    assert "stage_became_head_ms=500.0" in msg


def test_timely_head_and_syncing_head_stay_quiet(caplog):
    from lighthouse_tpu.beacon_chain.block_times_cache import BlockTimesCache

    clock = ManualSlotClock(seconds_per_slot=12)
    cache = BlockTimesCache(slot_clock=clock, seconds_per_slot=12)
    with caplog.at_level(logging.WARNING, logger="lighthouse_tpu"):
        # timely: within the deadline
        clock.set_slot(1)
        clock.set_seconds_into_slot(2.0)
        cache.set_became_head(b"\x03" * 32, 1, 10.0)
        # catch-up: hours late relative to its own slot, but the clock is
        # far ahead — range sync must not flood the log
        clock.set_slot(500)
        clock.set_seconds_into_slot(2.0)
        cache.set_became_head(b"\x04" * 32, 3, 20.0)
    assert not [
        r for r in caplog.records if "late head block" in r.getMessage()
    ]


def test_attestation_observation_delay_histograms():
    h = _harness(slots=2)
    hist = REGISTRY.histogram(
        "beacon_attestation_gossip_slot_start_delay_seconds"
    )
    before = hist.count
    slot = h.chain.head_state.slot
    h.slot_clock.set_seconds_into_slot(3.5)
    atts = h.make_unaggregated_attestations(slot, h.chain.head_root)
    h.chain.process_attestation_batch(atts)
    assert hist.count >= before + len(atts)


# -- queue observability -----------------------------------------------------


def test_queue_wait_and_work_histograms_populated():
    bp = BeaconProcessor(num_workers=1, name="obs-queue-test")
    try:
        wait = REGISTRY.histogram("beacon_processor_queue_wait_seconds_api_request")
        run = REGISTRY.histogram("beacon_processor_work_seconds_api_request")
        busy = REGISTRY.counter("beacon_processor_busy_seconds_total")
        w0, r0, b0 = wait.count, run.count, busy.value()
        for i in range(5):
            bp.submit(WorkType.API_REQUEST, i, lambda item: time.sleep(0.001))
        assert bp.drain(timeout=5.0)
        assert wait.count == w0 + 5  # one wait sample per event
        assert run.count == r0 + 5  # singletons: one run sample per event
        assert busy.value() > b0  # busy-seconds accumulated
        assert REGISTRY.gauge("beacon_processor_workers_total").value() == 1.0
    finally:
        bp.shutdown()


def test_sync_sim_populates_chain_segment_queue_waits():
    """The acceptance sim: a real two-node catch-up through the range-sync
    state machine rides the CHAIN_SEGMENT queue and must leave
    time-in-queue samples behind."""
    from lighthouse_tpu.network import NetworkService

    a = _harness(slots=E.SLOTS_PER_EPOCH)
    b = _harness()
    wait = REGISTRY.histogram("beacon_processor_queue_wait_seconds_chain_segment")
    run = REGISTRY.histogram("beacon_processor_work_seconds_chain_segment")
    w0, r0 = wait.count, run.count
    na = NetworkService(a.chain, heartbeat_interval=None).start()
    nb = NetworkService(b.chain, heartbeat_interval=None).start()
    try:
        b.slot_clock.set_slot(a.chain.head_state.slot)
        peer = nb.connect("127.0.0.1", na.port)
        imported = nb.sync.sync_with(peer)
        assert imported == E.SLOTS_PER_EPOCH
    finally:
        na.stop()
        nb.stop()
    assert wait.count > w0, "range-sync batches must record time-in-queue"
    assert run.count > r0, "range-sync batches must record handler run time"


# -- validator monitor satellite ---------------------------------------------


def test_validator_monitor_columnar_and_bounded():
    from lighthouse_tpu.beacon_chain.validator_monitor import (
        MAX_INCLUSION_DELAY_SLOTS,
        MonitoredValidator,
    )

    h = _harness()
    mon = h.chain.validator_monitor
    for i in range(16):
        mon.add_validator(i)
    h.extend_chain(2 * E.SLOTS_PER_EPOCH)
    v0 = mon.summary(0)
    # the columnar path still credits inclusions with sane delays
    assert v0.attestations_included >= 1
    assert all(d >= 1 for d in v0.inclusion_delays.values())

    # the bound: a long soak can't grow the per-validator dict forever
    mv = MonitoredValidator(index=0, pubkey=b"")
    for slot in range(MAX_INCLUSION_DELAY_SLOTS * 3):
        assert mv.record_inclusion(slot, 1)
    assert len(mv.inclusion_delays) == MAX_INCLUSION_DELAY_SLOTS
    # oldest evicted, newest retained
    assert (MAX_INCLUSION_DELAY_SLOTS * 3 - 1) in mv.inclusion_delays
    assert 0 not in mv.inclusion_delays
    # dedup still works within the retained window
    assert not mv.record_inclusion(MAX_INCLUSION_DELAY_SLOTS * 3 - 1, 2)


# -- overhead guard ----------------------------------------------------------


@pytest.mark.perf_smoke
def test_trace_collection_overhead_bounded(monkeypatch):
    """Collection-on vs collection-off block import: the tree assembly +
    collector delivery must stay within a calibrated bound. Median-of-N
    per mode; the bound is loose (2× + 50 ms absolute floor) because
    minimal-preset imports are single-digit ms and CI boxes are noisy —
    what it catches is an accidental O(spans²) walk or a lock on the
    import path, not a 5% regression."""
    import statistics

    def run_mode(collect: str) -> float:
        monkeypatch.setenv("LIGHTHOUSE_TPU_TRACE_COLLECT", collect)
        h = _harness()
        times = []
        for _ in range(8):
            slot = h.chain.head_state.slot + 1
            t0 = time.perf_counter()
            h.add_block_at_slot(slot)
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    off = run_mode("0")
    on = run_mode("1")
    assert on <= off * 2.0 + 0.05, (
        f"trace collection overhead out of bounds: on={on * 1000:.2f}ms "
        f"off={off * 1000:.2f}ms"
    )
