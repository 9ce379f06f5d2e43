"""Stage spans on the gossip attestation path, and the bridge that puts
every span on a JAX profiler trace.

A span opened while a profiler trace runs is a TraceMe of the same name
in the recorded `.xplane.pb`, nested in its parent; with no trace running
no TraceMe is made. The device verifier records one `bls_device_verify`
span a chunk with a child span for each stage and a `bls_device_wait`
span for each of its two blocking reads (checked here with its device
programs replaced by stand-ins; `test_device_bls.py` checks the real
ones), its pairing stage ends in one `bls_pairing_host_tail` span,
outside the waits, and a chunk launches a fixed handful of device
programs with no eager device op between them;
`process_attestation_batch` records its prepare, fallback and apply
stages."""

import glob
import random

import jax
import jax.numpy as jnp
import pytest

import lighthouse_tpu.ops.bls381_verify as ops_full
from lighthouse_tpu.ops.bls381_pairing import PAIRING_LANES
from lighthouse_tpu.ops.bls381_tower import f12_ones
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.utils import tracing
from lighthouse_tpu.utils.tracing import span

DEVICE_STAGES = [
    "bls_device_decode",
    "bls_device_hash_to_g2",
    "bls_device_aggregate",
    "bls_device_ladders",
    "bls_device_message_groups",
    "bls_device_subgroup",
    "bls_device_pairing",
]


def _walk(s):
    yield s
    for c in s.children:
        yield from _walk(c)


# -- the profiler bridge -----------------------------------------------------


def test_span_lands_in_profiler_trace_nested(tmp_path):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("obs_bridge_outer"):
            with span("obs_bridge_inner", sets=3):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs_bridge_"):
                    events[e.name] = (line.name, e.start_ns, e.end_ns, dict(e.stats))
    outer, inner = events["obs_bridge_outer"], events["obs_bridge_inner"]
    assert outer[0] == inner[0]  # one thread's line
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[3] == {"sets": 3}


def test_no_traceme_without_an_active_trace(monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    class Counting(real):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert not real.is_enabled()
    with span("obs_bridge_off"):
        pass
    assert made == []
    # the same span while a trace is active opens exactly one TraceMe
    monkeypatch.setattr(Counting, "is_enabled", staticmethod(lambda: True))
    with span("obs_bridge_on"):
        pass
    assert made == ["obs_bridge_on"]
    assert tracing.current_span() is None


# -- the device verifier's stages --------------------------------------------


@pytest.fixture(autouse=True)
def host_backend():
    before = bls.get_backend().name
    bls.set_backend("host")
    yield
    bls.set_backend(before)


#: the device programs of one valid chunk, in launch order
CHUNK_PROGRAMS = [
    "hash_to_g2_device",
    "_aggregate",
    "_ladders",
    "_message_groups",
    "_pairing_inputs",
    "_miller_jit",
]


def _program(name, fn):
    fn.__name__ = name
    return jax.jit(fn)


def _stand_in_kernels(monkeypatch, in_subgroup=True):
    """Shape-preserving stand-ins for the verifier's device programs, each
    a jitted program of the same name: the host staging, the stage spans,
    the counters and the pairing's host tail (over Miller lanes that all
    hold one) run for real. The ladders' stand-in reports every signature
    in the subgroup, or none."""
    lanes = lambda: jnp.zeros((PAIRING_LANES, 48), jnp.int32)  # noqa: E731
    lanes2 = lambda: jnp.zeros((PAIRING_LANES, 2, 48), jnp.int32)  # noqa: E731
    for name, fn in {
        "_aggregate": lambda grid, slices: tuple(c[:, 0] for c in grid),
        "_ladders": lambda agg, sigs, bits: (
            agg,
            (sigs[0][:1], sigs[1][:1], sigs[0][:1]),
            jnp.full(sigs[2].shape, in_subgroup),
        ),
        "_message_groups": lambda s_pk, idx, slices: s_pk,
        "hash_to_g2_device": lambda u: (u[:, 0], u[:, 1], u[:, 0]),
        "_pairing_inputs": lambda *a: (
            lanes(), lanes(), lanes2(), lanes2(), jnp.zeros(PAIRING_LANES, bool)
        ),
        "_miller_jit": lambda xp, yp, qx, qy: f12_ones(xp.shape[:1]),
    }.items():
        monkeypatch.setattr(ops_full, name, _program(name, fn))


def _device_executions(monkeypatch):
    """Every device program JAX runs from here on, by name, eager ops
    included: the C++ dispatch path is cleared and kept off, so each
    execution passes through `ExecuteReplicated`."""
    from jax._src import pjit
    from jax._src.interpreters import pxla

    ran = []
    execute = pxla.ExecuteReplicated.__call__

    def counting(self, *args):
        ran.append(self.name)
        return execute(self, *args)

    monkeypatch.setattr(pxla.ExecuteReplicated, "__call__", counting)
    monkeypatch.setattr(pjit, "_get_fastpath_data", lambda *a, **k: None)
    jax.clear_caches()
    return ran


def _eager_primitives(monkeypatch):
    """Primitives applied one by one outside any program."""
    from jax._src import dispatch

    applied = []
    apply = dispatch.apply_primitive

    def counting(prim, *args, **params):
        applied.append(prim.name)
        return apply(prim, *args, **params)

    monkeypatch.setattr(dispatch, "apply_primitive", counting)
    return applied


def _counts():
    return {
        k: REGISTRY.counter(k).value()
        for k in (
            "bls_device_batches_total", "bls_device_sets_total", "bls_device_set_lanes_total",
            "bls_pairing_host_tails_total", "bls_device_programs_total",
        )
    }


def test_device_verifier_records_each_stage_once(monkeypatch):
    _stand_in_kernels(monkeypatch)
    kps = bls.interop_keypairs(3)
    msgs = [b"\x01" * 32, b"\x01" * 32, b"\x02" * 32]
    sets = [bls.SignatureSet.single(kp.sk.sign(m), kp.pk, m) for kp, m in zip(kps, msgs)]
    before = _counts()
    with span("obs_device_root") as root:
        assert ops_full.verify_signature_sets_device_full(sets, random.Random(1))
    after = _counts()
    assert {k: after[k] - before[k] for k in after} == {
        "bls_device_batches_total": 1,
        "bls_device_sets_total": 3,
        "bls_device_set_lanes_total": 32,
        "bls_pairing_host_tails_total": 1,
        "bls_device_programs_total": len(CHUNK_PROGRAMS),
    }
    (call,) = root.children
    assert call.name == "bls_device_verify"
    assert call.fields == {"sets": 3, "lanes": 32, "keys": 16, "programs": len(CHUNK_PROGRAMS)}
    assert [c.name for c in call.children] == DEVICE_STAGES
    stages = {c.name: c for c in call.children}
    waits = [s for s in _walk(call) if s.name == "bls_device_wait"]
    assert len(waits) == 2
    assert [w.name for w in stages["bls_device_subgroup"].children] == ["bls_device_wait"]
    pairing = stages["bls_device_pairing"]
    assert [c.name for c in pairing.children] == ["bls_device_wait", "bls_pairing_host_tail"]
    (tail,) = [s for s in _walk(call) if s.name == "bls_pairing_host_tail"]
    assert tail.fields == {"lanes": PAIRING_LANES}
    assert all(tail not in list(_walk(w)) for w in waits)
    # the stages cover the call: only the span bookkeeping falls between
    assert sum(c.duration_s for c in call.children) <= call.duration_s


def test_device_verifier_chunks_get_a_span_each(monkeypatch):
    _stand_in_kernels(monkeypatch)
    monkeypatch.setenv("LIGHTHOUSE_TPU_BLS_CHUNK", "2")
    kp = bls.interop_keypairs(1)[0]
    msg = b"\x03" * 32
    sets = [bls.SignatureSet.single(kp.sk.sign(msg), kp.pk, msg)] * 3
    before = _counts()
    with span("obs_device_root") as root:
        assert bls._TpuBackend().verify_signature_sets(sets)
    after = _counts()
    calls = [c for c in root.children if c.name == "bls_device_verify"]
    assert [c.fields["sets"] for c in calls] == [2, 1]
    for c in calls:
        assert [s.name for s in c.children] == DEVICE_STAGES
        (pairing,) = [s for s in c.children if s.name == "bls_device_pairing"]
        assert [s.name for s in pairing.children] == ["bls_device_wait", "bls_pairing_host_tail"]
    # one host tail a chunk: the tail counter moves with the batch counter
    moved = {k: after[k] - before[k] for k in after}
    assert moved["bls_pairing_host_tails_total"] == moved["bls_device_batches_total"] == 2


@pytest.mark.parametrize("keys", [1, 496], ids=["att", "import"])
def test_device_verifier_chunk_launches_its_programs_only(monkeypatch, keys):
    """One chunk is a fixed handful of device programs and nothing else:
    no eager op runs on the device between them, whatever the committee
    size (an att-shaped chunk of single keys, an import-shaped one of
    496-key rows, bucketed to 512)."""
    _stand_in_kernels(monkeypatch)
    kps = bls.interop_keypairs(3)
    msgs = [b"\x04" * 32, b"\x04" * 32, b"\x05" * 32]
    sets = [
        bls.SignatureSet(kp.sk.sign(m), [kp.pk] * keys, m) for kp, m in zip(kps, msgs)
    ]
    before = _counts()["bls_device_programs_total"]
    ran = _device_executions(monkeypatch)
    applied = _eager_primitives(monkeypatch)
    with span("obs_device_root") as root:
        assert ops_full.verify_signature_sets_device_full(sets, random.Random(3))
    assert applied == []
    assert ran == [f"jit({name})" for name in CHUNK_PROGRAMS]
    (call,) = root.children
    assert call.fields == {
        "sets": 3, "lanes": 32, "keys": 16 if keys == 1 else 512, "programs": len(ran)
    }
    assert _counts()["bls_device_programs_total"] - before == len(ran)


def test_device_verifier_counts_the_programs_of_a_rejected_chunk(monkeypatch):
    """A chunk that fails the subgroup check stops at the verdict's read,
    before the pairing's two programs, and the span and the counter say
    so."""
    _stand_in_kernels(monkeypatch, in_subgroup=False)
    kp = bls.interop_keypairs(1)[0]
    msg = b"\x06" * 32
    before = _counts()["bls_device_programs_total"]
    with span("obs_device_root") as root:
        assert not ops_full.verify_signature_sets_device_full(
            [bls.SignatureSet.single(kp.sk.sign(msg), kp.pk, msg)], random.Random(4)
        )
    (call,) = root.children
    assert call.fields["programs"] == len(CHUNK_PROGRAMS) - 2
    assert _counts()["bls_device_programs_total"] - before == len(CHUNK_PROGRAMS) - 2


# -- the gossip batch's stages -----------------------------------------------


@pytest.fixture
def attestations():
    from lighthouse_tpu.beacon_chain import BeaconChainHarness
    from lighthouse_tpu.types import MinimalEthSpec, minimal_spec

    h = BeaconChainHarness(minimal_spec(), MinimalEthSpec, validator_count=64)
    h.extend_chain(2, attest=False)
    chain = h.chain
    return chain, h.make_unaggregated_attestations(chain.head_state.slot, chain.head_root)


@pytest.mark.parametrize("invalid", [False, True])
def test_attestation_batch_stages(attestations, invalid):
    chain, atts = attestations
    if invalid:
        atts[3].signature = atts[4].signature  # a valid point, the wrong signer
    with span("obs_batch_root") as root:
        results = chain.process_attestation_batch(atts)
    assert sum(isinstance(r, Exception) for r in results) == int(invalid)
    (batch,) = root.children
    assert batch.name == "attestation_batch"
    stages = [c.name for c in batch.children if c.name.startswith("attestation_")]
    want = ["attestation_prepare"] + ["attestation_fallback"] * invalid + ["attestation_apply"]
    assert stages == want
    (apply,) = [c for c in batch.children if c.name == "attestation_apply"]
    assert [c.name for c in apply.children] == ["import_lock_wait"]
    assert apply.fields == {"accepted": len(atts) - invalid}
