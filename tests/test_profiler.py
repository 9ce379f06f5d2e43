"""Continuous profiling: span-attributed stack sampler + endpoints.

PR 10's acceptance suite: the sampler is off by default and leaks zero
threads, samples attribute to the innermost active span (including
across the beacon_processor `copy_context` worker hop), the collapsed /
speedscope exports hold their golden shapes, `/lighthouse/profile` and
`/lighthouse/health` serve from BOTH the MetricsServer and the Beacon
API, and a perf_smoke bound keeps
sampled block-import wall time within 1.10× of unsampled."""

import json
import threading
import time
import urllib.error
import urllib.request
from dataclasses import replace

import pytest

from lighthouse_tpu.beacon_processor import BeaconProcessor, WorkType
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.metrics.profiler import (
    PROFILER,
    StackProfiler,
    maybe_start_profiler,
)
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
from lighthouse_tpu.utils import tracing
from lighthouse_tpu.utils.tracing import adopt_thread_span, span


def _harness(slots=0, validator_count=16):
    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=validator_count)
    if slots:
        h.extend_chain(slots, attest=False)
    return h


# -- off by default / zero thread leak ---------------------------------------


def test_profiler_off_by_default_no_threads(monkeypatch):
    monkeypatch.delenv("LIGHTHOUSE_TPU_PROFILE", raising=False)
    before = threading.active_count()
    assert maybe_start_profiler() is None
    assert not PROFILER.running
    # server starts must not arm it either
    from lighthouse_tpu.metrics.server import MetricsServer

    srv = MetricsServer().start()
    try:
        assert not PROFILER.running
        assert not any(
            t.name == "stack-profiler" for t in threading.enumerate()
        )
    finally:
        srv.stop()
        srv._thread.join(timeout=2.0)
    # the only threads that came and went were the server's own
    assert threading.active_count() <= before + 1


def test_profiler_start_stop_no_thread_leak():
    p = StackProfiler(hz=200)
    before = threading.active_count()
    p.start()
    assert p.running
    assert any(t.name == "stack-profiler" for t in threading.enumerate())
    p.stop()
    assert not p.running
    assert threading.active_count() == before
    # idempotent stop, restartable
    p.stop()
    p.start()
    p.stop()
    assert threading.active_count() == before


# -- span attribution --------------------------------------------------------


def test_sample_attributes_to_innermost_span_root():
    p = StackProfiler(hz=100)
    with span("obs_prof_root"):
        with span("inner_stage"):
            assert p.sample_once() >= 1
    snap = p.snapshot()
    # attribution is by the TRACE ROOT name, not the innermost span name
    assert "obs_prof_root" in snap
    (stack, count), *_ = sorted(
        snap["obs_prof_root"].items(), key=lambda kv: -kv[1]
    )
    assert count >= 1
    assert stack.startswith("thread:")
    assert "sample_once" in stack  # the sampled frame chain reached here


def test_sample_without_span_is_unattributed():
    p = StackProfiler(hz=100)
    assert tracing.thread_spans().get(threading.get_ident()) is None
    p.sample_once()
    assert "unattributed" in p.snapshot()
    assert REGISTRY.counter("profiler_samples_total").value(
        root="unattributed"
    ) > 0


def test_thread_registry_restores_on_exit():
    ident = threading.get_ident()
    with span("outer_reg") as outer:
        assert tracing.thread_spans()[ident] is outer
        with span("inner_reg") as inner:
            assert tracing.thread_spans()[ident] is inner
        assert tracing.thread_spans()[ident] is outer
    assert ident not in tracing.thread_spans()


def test_adopt_thread_span_attribution():
    """The worker-hop primitive in isolation: a foreign span adopted for
    a block attributes this thread's samples to its root."""
    p = StackProfiler(hz=100)
    foreign = span("obs_adopt_root")
    with foreign:
        pass  # closed; adoption only reads root_name
    ident = threading.get_ident()
    with adopt_thread_span(foreign):
        assert tracing.thread_spans()[ident] is foreign
        p.sample_once()
    assert ident not in tracing.thread_spans()
    assert "obs_adopt_root" in p.snapshot()


def test_worker_hop_samples_attribute_to_submitting_root():
    """The beacon_processor contract: a handler running on a worker
    thread (inside the submitter's copied context) is sampled under the
    SUBMITTING span's root even while outside any span of its own."""
    p = StackProfiler(hz=100)
    bp = BeaconProcessor(num_workers=1, name="prof-test")
    sampled = threading.Event()

    def handler(item):
        # no span opened here: attribution must come from adoption
        p.sample_once()
        sampled.set()

    try:
        with span("obs_prof_submit_root"):
            assert bp.submit(WorkType.API_REQUEST, "x", handler)
            assert bp.drain(timeout=5.0)
        assert sampled.wait(timeout=1.0)
    finally:
        bp.shutdown()
    snap = p.snapshot()
    assert "obs_prof_submit_root" in snap
    stacks = "\n".join(snap["obs_prof_submit_root"])
    # the worker thread's kind rides the folded stack
    assert "thread:prof-test-w" in stacks


# -- export golden shapes ----------------------------------------------------


def _populated_profiler():
    p = StackProfiler(hz=100)
    with span("obs_prof_shape"):
        for _ in range(3):
            p.sample_once()
    p.sample_once()  # one unattributed sweep
    return p


def test_collapsed_golden_shape():
    p = _populated_profiler()
    text = p.collapsed()
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        stack, _, count = line.rpartition(" ")
        assert int(count) >= 1
        parts = stack.split(";")
        # root;thread:<kind>;frames...
        assert len(parts) >= 3
        assert parts[1].startswith("thread:")
    roots = {line.split(";", 1)[0] for line in text.strip().splitlines()}
    assert {"obs_prof_shape", "unattributed"} <= roots
    # root filter narrows to one root
    only = p.collapsed("obs_prof_shape")
    assert all(
        line.startswith("obs_prof_shape;")
        for line in only.strip().splitlines()
    )


def test_speedscope_golden_shape():
    p = _populated_profiler()
    doc = p.speedscope()
    assert set(doc) == {"$schema", "shared", "profiles", "name", "exporter"}
    assert doc["$schema"] == "https://www.speedscope.app/file-format-schema.json"
    assert set(doc["shared"]) == {"frames"}
    names = [prof["name"] for prof in doc["profiles"]]
    assert "obs_prof_shape" in names and "unattributed" in names
    nframes = len(doc["shared"]["frames"])
    for prof in doc["profiles"]:
        assert set(prof) == {
            "type", "name", "unit", "startValue", "endValue", "samples",
            "weights",
        }
        assert prof["type"] == "sampled" and prof["unit"] == "none"
        assert len(prof["samples"]) == len(prof["weights"])
        assert prof["endValue"] == float(sum(prof["weights"]))
        for s in prof["samples"]:
            assert all(0 <= i < nframes for i in s)
    json.dumps(doc)  # JSON-serializable as-is


def test_root_other_query_covers_non_taxonomy_roots():
    """profiler_samples_total folds non-taxonomy roots into its `other`
    label; a `root=other` query must return those same roots' stacks so
    the metric's aggregate and the endpoint agree."""
    p = StackProfiler(hz=100)
    with span("obs_nontaxonomy_root"):
        p.sample_once()
    snap = p.snapshot("other")
    assert "obs_nontaxonomy_root" in snap
    # taxonomy roots and the unattributed bucket are NOT in `other`
    with span("block_import"):
        p.sample_once()
    p.sample_once()  # unattributed sweep
    snap = p.snapshot("other")
    assert "block_import" not in snap and "unattributed" not in snap


def test_top_stacks_and_decay_bounds():
    p = StackProfiler(hz=100, max_stacks_per_root=4)
    with p._lock:
        p._stacks["obs_decay_root"] = {f"thread:t;f{i}": float(i + 1)
                                       for i in range(40)}
        p._samples_since_decay = 10 ** 9
        p._decay_locked()
    per = p.snapshot()["obs_decay_root"]
    assert len(per) <= 4  # pruned back to the per-root bound
    assert max(per.values()) == 20  # counts halved
    top = p.top_stacks(n=2)["obs_decay_root"]
    assert [e["samples"] for e in top] == sorted(
        (e["samples"] for e in top), reverse=True
    )
    assert set(top[0]) == {"stack", "samples"}


# -- endpoints on both servers -----------------------------------------------


def test_profile_endpoint_disabled_returns_503(monkeypatch):
    from lighthouse_tpu.metrics import profiler as profiler_mod
    from lighthouse_tpu.metrics.server import MetricsServer

    monkeypatch.delenv("LIGHTHOUSE_TPU_PROFILE", raising=False)
    monkeypatch.setattr(profiler_mod, "PROFILER", StackProfiler())
    srv = MetricsServer().start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/lighthouse/profile"
            )
        assert exc_info.value.code == 503
    finally:
        srv.stop()


def test_health_endpoint_on_both_servers():
    from lighthouse_tpu.http_api import HttpApiServer
    from lighthouse_tpu.metrics.server import MetricsServer

    h = _harness()
    msrv = MetricsServer().start()
    asrv = HttpApiServer(h.chain).start()
    api_traces_before = REGISTRY.counter("trace_collector_traces_total").value(
        root="api_request"
    )
    try:
        for port in (msrv.port, asrv.port):
            doc = json.load(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/lighthouse/health"
                )
            )["data"]
            assert doc["uptime_seconds"] > 0
            assert doc["rss_bytes"] > 0
            assert doc["peak_rss_bytes"] >= doc["rss_bytes"] > 0
            assert doc["threads"] >= 2
            assert len(doc["gc"]["counts"]) == 3
            assert len(doc["gc"]["collections"]) == 3
            assert 0.0 <= doc["worker_busy_ratio"] <= 1.0
            assert "sync_state" in doc and "trace_ring_size" in doc
            assert set(doc["profiler"]) == {"running", "samples"}
            assert "total_memory_bytes" in doc["system"]
    finally:
        msrv.stop()
        asrv.stop()
    # observability reads never mint api_request traces
    assert (
        REGISTRY.counter("trace_collector_traces_total").value(
            root="api_request"
        )
        == api_traces_before
    )


# -- THE acceptance sim ------------------------------------------------------


def test_gossip_driven_import_profiles_to_block_import(monkeypatch):
    """Acceptance: with LIGHTHOUSE_TPU_PROFILE=1 a gossip-driven block
    import sim yields ≥1 block_import-attributed stack retrievable as
    collapsed text AND speedscope JSON from both servers, and worker-hop
    (chain-segment) samples attribute to sync_range_batch rather than
    the unattributed bucket."""
    from lighthouse_tpu.http_api import HttpApiServer
    from lighthouse_tpu.metrics import profiler as profiler_mod
    from lighthouse_tpu.metrics.server import MetricsServer
    from lighthouse_tpu.network import NetworkService

    monkeypatch.setenv("LIGHTHOUSE_TPU_PROFILE", "1")
    # dense sampling so single-digit-ms minimal-preset imports land
    monkeypatch.setenv("LIGHTHOUSE_TPU_PROFILE_HZ", "750")
    a = _harness(slots=E.SLOTS_PER_EPOCH)
    b = _harness()
    msrv = MetricsServer().start()  # arms the sampler from the env
    asrv = HttpApiServer(b.chain).start()
    prof = profiler_mod.PROFILER
    assert prof.running
    na = NetworkService(a.chain, heartbeat_interval=None).start()
    nb = NetworkService(b.chain, heartbeat_interval=None).start()
    try:
        # range-sync catch-up: imports ride the beacon_processor
        # CHAIN_SEGMENT lane — the copy_context worker hop under test
        b.slot_clock.set_slot(a.chain.head_state.slot)
        peer = nb.connect("127.0.0.1", na.port)
        assert nb.sync.sync_with(peer) == E.SLOTS_PER_EPOCH
        time.sleep(0.2)  # let A's inbound-peer registration settle

        # alternate the two import paths until both show up in the
        # profile: gossip-published blocks (block_import ROOT spans on
        # B's gossip handler thread) and quiet extensions pulled through
        # range sync (CHAIN_SEGMENT worker lane → sync_range_batch)
        def worker_attributed(snap):
            return any(
                "thread:network_beacon_processor-w" in s
                for s in snap.get("sync_range_batch", ())
            )

        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            snap = prof.snapshot()
            if "block_import" in snap and worker_attributed(snap):
                break
            # one gossip-driven import
            slot = a.chain.head_state.slot + 1
            a.slot_clock.set_slot(slot)
            b.slot_clock.set_slot(slot)
            root, signed = a.add_block_at_slot(slot)
            na.publish_block(signed)
            arrival = time.monotonic() + 5.0
            while time.monotonic() < arrival and b.chain.head_root != root:
                time.sleep(0.02)
            assert b.chain.head_root == root
            # a quiet 4-slot extension, range-synced through the workers
            for _ in range(4):
                slot = a.chain.head_state.slot + 1
                a.slot_clock.set_slot(slot)
                a.add_block_at_slot(slot)
            b.slot_clock.set_slot(a.chain.head_state.slot)
            nb.sync.sync_with(peer)
        snap = prof.snapshot()
        assert "block_import" in snap, f"roots sampled: {sorted(snap)}"
        # worker-hop attribution: chain-segment samples landed under the
        # sync_range_batch root, NOT in the unattributed bucket
        assert worker_attributed(snap), (
            f"roots sampled: {sorted(snap)}; sync stacks: "
            f"{sorted(snap.get('sync_range_batch', ()))[:4]}"
        )

        # retrievable from BOTH servers, both formats
        for port in (msrv.port, asrv.port):
            text = (
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}"
                    "/lighthouse/profile?root=block_import&format=collapsed"
                )
                .read()
                .decode()
            )
            assert text.startswith("block_import;thread:")
            doc = json.load(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/lighthouse/profile"
                )
            )
            names = [p_["name"] for p_ in doc["profiles"]]
            assert "block_import" in names
        # the eager counter moved for the taxonomy root
        assert (
            REGISTRY.counter("profiler_samples_total").value(
                root="block_import"
            )
            > 0
        )
    finally:
        na.stop()
        nb.stop()
        msrv.stop()
        asrv.stop()
        profiler_mod.stop_profiler()
    assert not prof.running


# -- RPC / gossip satellite metrics ------------------------------------------


def test_rpc_latency_histograms_populated():
    from lighthouse_tpu.network import NetworkService
    from lighthouse_tpu.network.rpc import RpcClient

    a = _harness(slots=4)
    na = NetworkService(a.chain, heartbeat_interval=None).start()
    s_status = REGISTRY.histogram("rpc_server_request_seconds_status")
    c_status = REGISTRY.histogram("rpc_client_request_seconds_status")
    s_range = REGISTRY.histogram(
        "rpc_server_request_seconds_beacon_blocks_by_range"
    )
    c_range = REGISTRY.histogram(
        "rpc_client_request_seconds_beacon_blocks_by_range"
    )
    c_md = REGISTRY.histogram("rpc_client_request_seconds_metadata")
    before = (s_status.count, c_status.count, s_range.count, c_range.count,
              c_md.count)
    try:
        b = _harness()
        nb = NetworkService(b.chain, heartbeat_interval=None).start()
        try:
            client = RpcClient("127.0.0.1", na.port)
            client.status(nb.local_status())
            client.metadata()
            blocks = client.blocks_by_range(1, 4, na.decode_block)
            assert len(blocks) == 4
        finally:
            nb.stop()
    finally:
        na.stop()
    after = (s_status.count, c_status.count, s_range.count, c_range.count,
             c_md.count)
    assert all(a_ > b_ for a_, b_ in zip(after, before)), (before, after)


def test_gossipsub_heartbeat_feeds_score_histogram_and_mesh_gauge():
    from lighthouse_tpu.network.gossipsub.behaviour import GossipsubBehaviour

    hist = REGISTRY.histogram("gossipsub_peer_score_distribution")
    before = hist.count
    sent = []
    topic = "/eth2/00000000/beacon_block/ssz_snappy"
    beh = GossipsubBehaviour(
        send=lambda p, f: sent.append(p),
        deliver=lambda t, d, o: True,
        mid_fn=lambda d: d[:20],
        seed=1,
    )
    beh.subscribe(topic)
    for i in range(3):
        beh.add_peer(f"p{i}")
        beh._handle_subscription(f"p{i}", True, topic)
    beh.heartbeat()
    assert hist.count == before + 3  # one observation per peer
    assert REGISTRY.gauge("gossipsub_mesh_peers").value(
        topic="beacon_block"
    ) == len(beh.mesh_peers(topic))
    assert len(beh.mesh_peers(topic)) == 3


def test_compile_cache_tracking(tmp_path):
    """The compile counters count from JAX's own build events: a program
    compiled into an empty persistent cache is one built and one miss;
    built again after the in-memory caches are cleared, it is one built
    and one hit. Trace, lower and compile seconds all grow."""
    import jax
    import numpy as np
    from jax._src import compilation_cache

    from lighthouse_tpu.utils import compile_cache as cc

    def snap():
        return (
            cc._BUILT.value(), cc._HITS.value(), cc._MISSES.value(),
            {p: cc._BUILD_SECONDS.value(phase=p) for p in ("trace", "lower", "compile")},
        )

    def build_once():
        # a host array in, so the call builds this one program and no other
        jax.jit(lambda x: x * 3 + 1)(np.arange(4)).block_until_ready()

    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    try:
        cc.enable_compile_cache()  # idempotent: the listeners register once
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jc"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compilation_cache.reset_cache()
        jax.clear_caches()
        built0, hits0, miss0, secs0 = snap()
        build_once()
        built1, hits1, miss1, secs1 = snap()
        assert (built1 - built0, hits1 - hits0, miss1 - miss0) == (1, 0, 1)
        assert all(secs1[p] > secs0[p] for p in secs0)
        jax.clear_caches()
        build_once()
        built2, hits2, miss2, _ = snap()
        assert (built2 - built1, hits2 - hits1, miss2 - miss1) == (1, 1, 0)
        # which program was built when
        assert cc.program_builds()[-1][1:2] == ("compile",)
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
        compilation_cache.reset_cache()
        jax.clear_caches()


@pytest.mark.parametrize("env_dir", [None, "/placed/from/outside"])
def test_compile_cache_dir_placement(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX (no directory
    is set in code); otherwise the cache sits at the fixed repo path."""
    import jax

    from lighthouse_tpu.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", env_dir)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        cc.enable_compile_cache()
        want = env_dir or cc.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == want
        assert cc.active_cache_dir() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# -- overhead guard ----------------------------------------------------------


@pytest.mark.perf_smoke
def test_sampled_block_import_overhead_bounded():
    """Acceptance bound: block import with the sampler running at the
    default rate stays within 1.10× of unsampled (plus a 10 ms absolute
    floor for timer noise on single-digit-ms minimal-preset imports).

    The modes alternate one import at a time on two warmed harnesses, the
    order flipping each round, so drift in the host's load lands on both
    sides alike; the sampler runs only around the sampled imports."""
    import statistics

    harnesses = {"off": _harness(), "on": _harness()}
    profiler = StackProfiler()  # default ~59 Hz
    times = {"off": [], "on": []}

    def import_one(mode):
        h = harnesses[mode]
        slot = h.chain.head_state.slot + 1
        if mode == "on":
            profiler.start()
        try:
            t0 = time.perf_counter()
            h.add_block_at_slot(slot)
            return time.perf_counter() - t0
        finally:
            if mode == "on":
                profiler.stop()

    for mode in harnesses:  # the first import pays one-time costs
        import_one(mode)
    for i in range(8):
        for mode in ("off", "on") if i % 2 == 0 else ("on", "off"):
            times[mode].append(import_one(mode))
    assert profiler.samples_total > 0
    off = statistics.median(times["off"])
    on = statistics.median(times["on"])
    assert on <= off * 1.10 + 0.010, (
        f"sampling overhead out of bounds: on={on * 1000:.2f}ms "
        f"off={off * 1000:.2f}ms"
    )
