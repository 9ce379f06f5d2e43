"""North-star benchmarks (BASELINE.md) on the live JAX backend.

Headline metric (the one JSON line): **bls_batch_verify_1k** — metric 1,
RLC batch verification of 1024 signature sets (64-pubkey committees, the
reference's gossip batch unit, beacon_processor/src/lib.rs:200). The
default lane is the HOST fast path (Pippenger MSM + fork-pool parallel
pairings, crypto/bls/_HostBackend) — the device lane's XLA compile has
blown every bench cap on this image in five rounds, so the lane that can
actually run is the headline; `BENCH_BLS_LANE=device` opts the device
verifier (ops/bls381_verify) back in, now with a compile-vs-execute time
split. Control for `vs_baseline` is the retained serial per-set RLC loop
(`verify_signature_sets_serial`) on a subsample, same run — blst is not
installable in this image, so the control is an honest same-machine CPU
implementation, NOT a blst number; see BENCH_NOTES.md.

Also measured (emitted in the same JSON line under "details", each with
median-of-N trials and min/max spread):
  * merkle_tree_hash_1M_leaves — metric 2 proxy: device level-batched
    SHA-256 Merkleization of a 1M-leaf array vs host hashlib.
  * block_import_ms — metric 5 at harness scale: full import pipeline
    (signature batch + state transition + fork choice) per block.

Prints a combined JSON line {"metric", "value", "unit", "vs_baseline",
"details"} after every completed metric; the LAST line on stdout is the
authoritative result (the driver reads the tail), so a timeout mid-run
leaves the best finished result instead of nothing.
"""

import hashlib
import json
import os
import random
import statistics
import sys
import time

import numpy as np

# BENCH_SMOKE=1 shrinks every metric to test-scale shapes (kernels already
# compiled by the test suite's persistent cache) — a fast wiring check on
# slow hosts; real numbers come from the full-size run.
SMOKE = os.environ.get("BENCH_SMOKE") == "1"

# Persistent compile cache: pairing-class kernels take minutes to compile;
# each --metric child enables it before its first trace (the parent that
# launches them never touches a JAX backend — a chip belongs to one process).
from lighthouse_tpu.utils.compile_cache import (  # noqa: E402
    compile_cache_stats,
    enable_compile_cache,
    track_device_compile,
)


def _partial(**kw):
    """Stream a progress line so a metric killed by the budget still leaves
    its completed per-trial/per-chunk timings behind: the parent collects
    `PARTIAL {...}` lines from the dead subprocess's stdout into the
    combined JSON's errors[metric]["partial"]."""
    print("PARTIAL " + json.dumps(kw), flush=True)


def _span_totals(names):
    """{span: (sum_s, count)} snapshot of the tracing histograms."""
    from lighthouse_tpu.metrics import REGISTRY

    out = {}
    for name in names:
        hist = REGISTRY.histogram(f"trace_span_seconds_{name}")
        out[name] = (hist.sum, hist.count)
    return out


def _span_deltas(before, after):
    """Per-stage mean_ms/samples between two `_span_totals` snapshots
    (stages with no new samples are omitted)."""
    stages = {}
    for name in before:
        d_sum = after[name][0] - before[name][0]
        d_count = after[name][1] - before[name][1]
        if d_count:
            stages[name] = {
                "mean_ms": round(d_sum / d_count * 1000, 2),
                "samples": d_count,
            }
    return stages


def _trials(fn, n=3, label="trial", between=None):
    out = []
    for i in range(n):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
        _partial(**{label: i + 1, "of": n, "s": round(out[-1], 4)})
        if between is not None:
            between()  # untimed inter-trial housekeeping (gc etc.)
    return {
        "median_s": statistics.median(out),
        "min_s": min(out),
        "max_s": max(out),
        "trials": n,
    }


def bench_merkle(jax):
    from lighthouse_tpu.ops.sha256 import (
        bytes_to_words,
        merkle_tree_levels,
        words_to_bytes,
    )

    n_leaves = 1 << 12 if SMOKE else 1 << 20
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=n_leaves * 32, dtype=np.uint8).tobytes()
    leaves = bytes_to_words(data)
    dev_leaves = jax.device_put(leaves)

    def run():
        root_words = merkle_tree_levels(dev_leaves)[0]
        jax.block_until_ready(root_words[0])
        return root_words

    with track_device_compile("merkle_tree_levels"):
        run()  # compile
    t = _trials(run, n=5)

    # host control on a 1/16 slice, extrapolated
    slice_leaves = n_leaves // 16
    slice_data = data[: slice_leaves * 32]

    def host_merkle_root(d):
        nodes = [d[i : i + 32] for i in range(0, len(d), 32)]
        while len(nodes) > 1:
            nodes = [
                hashlib.sha256(nodes[i] + nodes[i + 1]).digest()
                for i in range(0, len(nodes), 2)
            ]
        return nodes[0]

    # pinned trial count; the control's own spread is reported so the
    # vs_baseline trend line carries its noise floor with it
    th = _trials(lambda: host_merkle_root(slice_data), n=3)
    host_s = th["median_s"] * 16

    # correctness spot-check
    got = words_to_bytes(merkle_tree_levels(jax.device_put(bytes_to_words(slice_data)))[0])[:32]
    assert got == host_merkle_root(slice_data), "merkle root mismatch!"

    return {
        "metric": "merkle_tree_hash_1M_leaves",
        "value": round(n_leaves / t["median_s"], 1),
        "unit": "leaves/sec",
        "vs_baseline": round(host_s / t["median_s"], 3),
        "baseline_control": "hashlib on a 1/16 slice x16 (spread below)",
        "spread": t,
        "control_spread": th,
    }


def _make_sets(bls, n_sets, committee):
    """n_sets aggregate-signature sets over one `committee`-key committee.

    The aggregate of per-key signatures on one message equals a single
    signature under the summed secret key (Σ skᵢ·H(m) = (Σ skᵢ)·H(m)), so
    generation costs one host sign per set instead of `committee` — and the
    result is cached on disk so the driver's bench run skips it entirely.
    """
    import pickle

    from lighthouse_tpu.crypto.bls import R

    cache = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        ".bench_cache",
        f"sets_v1_{n_sets}x{committee}.pkl",
    )
    kps = bls.interop_keypairs(committee)
    pks = [kp.pk for kp in kps]  # shared objects: 64 decompressions, not 64k
    msgs = [
        hashlib.sha256(b"att" + i.to_bytes(4, "little")).digest()
        for i in range(n_sets)
    ]
    if os.path.exists(cache):
        with open(cache, "rb") as f:
            sig_bytes = pickle.load(f)
        if len(sig_bytes) == n_sets:
            return [
                bls.SignatureSet(bls.Signature(sb), pks, m)
                for sb, m in zip(sig_bytes, msgs)
            ]
    sk_agg = bls.SecretKey(sum(kp.sk.scalar for kp in kps) % R)
    sets = [
        bls.SignatureSet(sk_agg.sign(m), pks, m) for m in msgs
    ]
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    with open(cache, "wb") as f:
        pickle.dump([s.signature.to_bytes() for s in sets], f)
    return sets


def bench_bls(jax):
    """Metric 1 dispatcher: host MSM+pool lane by default (the lane this
    box can actually complete), device lane opt-in via BENCH_BLS_LANE."""
    if os.environ.get("BENCH_BLS_LANE", "host") == "device":
        return _bench_bls_device(jax)
    return _bench_bls_host(jax)


def _bench_bls_host(jax):
    """Host fast path: one G2 MSM over the RLC'd signatures, bilinearity
    regrouping of the per-set pairings (2 pairs for the gossip-batch
    shape instead of 1025), Miller loops sharded across the fork pool.
    Control = the retained serial per-set loop on a 1/16 subsample,
    extrapolated, in the SAME run (warm caches for both lanes)."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.parallel import host_pool

    bls.set_backend("host")
    n_sets, committee = (9, 3) if SMOKE else (1024, 64)
    sets = _make_sets(bls, n_sets, committee)
    host = bls._BACKENDS["host"]
    pool = host_pool.get_pool()

    def run():
        assert host.verify_signature_sets(sets, random.Random(5))

    t0 = time.perf_counter()
    run()  # warm: hash_to_g2 + decompression caches fill, pool forks
    warm_s = time.perf_counter() - t0
    _partial(phase="warm", s=round(warm_s, 2))

    _SPANS = ("bls_msm_g2", "bls_parallel_pairing")
    before = _span_totals(_SPANS)
    t = _trials(run, n=3)
    stages = _span_deltas(before, _span_totals(_SPANS))

    # same-run serial control (the pre-MSM per-set loop), subsampled —
    # the full serial run is ~n_sets × 13 ms of wNAF ladders + Miller
    # loops and scales linearly in sets, so a 1/16 slice ×16 is honest
    ctrl_sets = sets[: max(8, n_sets // 16)]

    def ctrl_run():
        assert host.verify_signature_sets_serial(ctrl_sets, random.Random(5))

    th = _trials(ctrl_run, n=3, label="control_trial")
    host_s = th["median_s"] * (n_sets / len(ctrl_sets))

    return {
        "metric": "bls_batch_verify_1k",
        "value": round(n_sets / t["median_s"], 2),
        "unit": "sets/sec",
        "vs_baseline": round(host_s / t["median_s"], 3),
        "baseline_control": (
            "serial per-set RLC loop (pre-MSM host path) on a 1/16 "
            "subsample x16, same run; see BENCH_NOTES.md"
        ),
        "config": {
            "sets": n_sets,
            "committee": committee,
            "lane": "host",
            "pool": pool.size,
            "pool_env": os.environ.get(host_pool.ENV_VAR),
            "warm_s": round(warm_s, 2),
        },
        "spread": t,
        "control_spread": th,
        "stages": stages,
        "cache": bls.cache_stats(),
    }


def _bench_bls_device(jax):
    """Device lane (opt-in): full on-device verifier in bounded-shape
    chunks, reporting a compile-vs-execute split so a timeout in either
    phase still says which phase ate the budget (every per-chunk timing
    streams as a PARTIAL line either way)."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.ops.bls381_verify import verify_signature_sets_device_full

    bls.set_backend("host")
    n_sets, committee = (9, 3) if SMOKE else (1024, 64)
    # The batch runs in identical-shape chunks: ONE set of compiled
    # kernels, reused across chunks, with fresh RLC randomness per chunk
    # (the security argument is per-batch). Default DEFAULT_DEVICE_CHUNK
    # (= 32, shared with the node's LIGHTHOUSE_TPU_BLS_CHUNK).
    # BENCH_BLS_CHUNK=0 sends the whole batch as one shape.
    chunk = 0 if SMOKE else int(
        os.environ.get("BENCH_BLS_CHUNK", str(bls.DEFAULT_DEVICE_CHUNK))
    )
    sets = _make_sets(bls, n_sets, committee)

    def dev_run(phase="execute"):
        if chunk:
            t0 = time.perf_counter()
            for i in range(0, n_sets, chunk):
                assert verify_signature_sets_device_full(
                    sets[i:i + chunk], random.Random(5 + i)
                )
                _partial(phase=phase, chunk_done=i // chunk + 1,
                         of=(n_sets + chunk - 1) // chunk,
                         elapsed_s=round(time.perf_counter() - t0, 2))
        else:
            assert verify_signature_sets_device_full(sets, random.Random(5))

    t0 = time.perf_counter()
    # compile-vs-execute through the standard metrics path: the warmup
    # rides a device_compile span and the compile_cache_{hits,misses}/
    # compile-seconds counters (reported below), not just phase labels
    with track_device_compile("bls381_verify"):
        dev_run(phase="compile")  # compile + cache warm
    compile_s = time.perf_counter() - t0
    _partial(phase="compile", s=round(compile_s, 2))
    t = _trials(dev_run, n=3)

    # same-run serial host control on a 1/16 slice, extrapolated
    ctrl_sets = sets[: max(8, n_sets // 16)]
    host = bls._BACKENDS["host"]

    def host_run():
        assert host.verify_signature_sets_serial(ctrl_sets, random.Random(5))

    th = _trials(host_run, n=3, label="control_trial")
    host_s = th["median_s"] * (n_sets / len(ctrl_sets))

    return {
        "metric": "bls_batch_verify_1k",
        "value": round(n_sets / t["median_s"], 2),
        "unit": "sets/sec",
        "vs_baseline": round(host_s / t["median_s"], 3),
        "baseline_control": (
            "serial per-set RLC loop (host, no blst in image); "
            "see BENCH_NOTES.md"
        ),
        "config": {"sets": n_sets, "committee": committee, "chunk": chunk,
                   "lane": "device"},
        "compile": {
            "s": round(compile_s, 2),
            "over_execute_s": round(compile_s - t["median_s"], 2),
        },
        "compile_cache": compile_cache_stats(),
        "spread": t,
    }


def bench_pairing(jax):
    """Host microbench for the optimized pairing path: one `pairing_check`
    of 2 pairs — the exact shape of a single signature verification and the
    `vs_baseline` control every device number is scored against. The old
    (reference) path is timed once alongside for the continuity record in
    BENCH_NOTES.md."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import cache_stats
    from lighthouse_tpu.crypto.bls12_381 import (
        FQ, G1_GEN, hash_to_g2, pairing_check, pt_neg,
    )
    from lighthouse_tpu.crypto.bls12_381 import pairing_reference

    bls.set_backend("host")
    sk = bls.interop_secret_key(0)
    pk_pt = sk.public_key().point()
    msg = hashlib.sha256(b"pairing microbench").digest()
    h = hash_to_g2(msg)
    sig_pt = sk.sign(msg).point()
    pairs = [(pk_pt, h), (pt_neg(FQ, G1_GEN), sig_pt)]

    def run():
        assert pairing_check(pairs)

    run()  # warm (builds the fixed-base/window tables)
    t = _trials(run, n=5)
    # ≥3-trial median for the control too — a single-trial control made
    # vs_baseline pure noise (BENCH_NOTES "Variance")
    tr = _trials(lambda: pairing_reference.pairing_check(pairs), n=3,
                 label="ref_trial")

    return {
        "metric": "pairing_check_ms",
        "value": round(t["median_s"] * 1000, 2),
        "unit": "ms/check (2 pairs, host)",
        "vs_baseline": round(tr["median_s"] / t["median_s"], 2),
        "baseline_control": "pairing_reference (pre-optimization host path)",
        "reference_ms": round(tr["median_s"] * 1000, 2),
        "spread": t,
        "control_spread": tr,
        "cache": cache_stats(),
    }


def bench_kzg(jax):
    """North-star metric 4: `verify_blob_kzg_proof_batch` on a 6-blob
    Deneb block (crypto/kzg/src/lib.rs:81-107). Device path = fused
    barycentric evaluations (ops/fr) + device multi-pairing; control =
    the same engine with the device disabled (host bigint). Blob set
    generation (12 MSMs) is disk-cached like the BLS sets."""
    import pickle
    import random as _r

    from lighthouse_tpu.crypto.kzg import FR_MODULUS, Kzg, TrustedSetup

    n_blobs = 2 if SMOKE else 6
    if SMOKE:
        setup = TrustedSetup.insecure_dev(64)
        n_domain = 64
    else:
        setup = TrustedSetup.default()
        n_domain = setup.n
    host = Kzg(setup)

    rng = _r.Random(33)
    blobs = [
        b"".join(
            rng.randrange(FR_MODULUS).to_bytes(32, "big")
            for _ in range(n_domain)
        )
        for _ in range(n_blobs)
    ]
    cache = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        ".bench_cache",
        f"kzg_v1_{n_blobs}x{n_domain}.pkl",
    )
    cs = proofs = None
    if os.path.exists(cache):
        with open(cache, "rb") as f:
            cs, proofs = pickle.load(f)
    if cs is None or len(cs) != n_blobs:
        cs = [host.blob_to_kzg_commitment(b) for b in blobs]
        proofs = [host.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, cs)]
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        with open(cache, "wb") as f:
            pickle.dump((cs, proofs), f)

    dev = Kzg(setup, device=True)

    def dev_run():
        assert dev.verify_blob_kzg_proof_batch(blobs, cs, proofs)

    with track_device_compile("kzg_verify_blob_batch"):
        dev_run()  # compile + cache warm
    assert dev._dev is not None, "device KZG fell back to host mid-bench"
    t = _trials(dev_run, n=3)

    def host_run():
        assert host.verify_blob_kzg_proof_batch(blobs, cs, proofs)

    # >=3 trials: a single-trial control made vs_baseline pure noise
    th = _trials(host_run, n=3)

    return {
        "metric": "kzg_verify_blob_batch_6",
        "value": round(t["median_s"] * 1000, 2),
        "unit": "ms/batch (6 blobs)",
        "vs_baseline": round(th["median_s"] / t["median_s"], 3),
        "baseline_control": "host bigint engine, same machine",
        "config": {"blobs": n_blobs, "domain": n_domain},
        "compile_cache": compile_cache_stats(),
        "spread": t,
        "control_spread": th,
    }


def bench_da_verify(jax):
    """PeerDAS cell-proof verification (das/proofs.py): a full block's
    worth of data-column cells collapsed into ONE RLC pairing check whose
    two sides are Pippenger MSMs sharded over the host fork pool.
    Headline: cells/sec through the batched lane at mainnet blob counts
    (6 blobs x 128 columns = 768 cells over the 4096-point domain).
    Control: the per-cell scalar oracle (`verify_cell_kzg_proof`, one
    full pairing check per cell) on a same-run subsample, extrapolated to
    cells/sec — the bench asserts the batched lane's >=5x and checks
    verdict parity on both a clean set and a tampered cell (batch False,
    oracle pinpointing the same cell). Proof GENERATION uses the
    insecure_dev setup's dev-tau fast path (one scalar mul per cell
    instead of a 4096-point quotient MSM); verification never shortcuts —
    the pairing math is identical for every setup, so the measured lane
    is honest."""
    import pickle
    import random as _r

    from lighthouse_tpu.crypto.kzg import FR_MODULUS, Kzg, TrustedSetup
    from lighthouse_tpu.das.proofs import (
        compute_cells_and_proofs,
        verify_cell_kzg_proof,
        verify_cell_kzg_proof_batch,
    )

    if SMOKE:
        n_blobs, n_domain, n_columns, oracle_n = 2, 64, 16, 4
    else:
        n_blobs, n_domain, n_columns, oracle_n = 6, 4096, 128, 24
    kzg = Kzg(TrustedSetup.insecure_dev(n_domain))

    rng = _r.Random(47)
    blobs = [
        b"".join(
            rng.randrange(FR_MODULUS).to_bytes(32, "big")
            for _ in range(n_domain)
        )
        for _ in range(n_blobs)
    ]
    cache = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        ".bench_cache",
        f"da_verify_v1_{n_blobs}x{n_domain}x{n_columns}.pkl",
    )
    sets = None
    if os.path.exists(cache):
        with open(cache, "rb") as f:
            sets = pickle.load(f)
    if sets is None or len(sets) != n_blobs:
        sets = [compute_cells_and_proofs(b, kzg, n_columns) for b in blobs]
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        with open(cache, "wb") as f:
            pickle.dump(sets, f)
    items = [
        (commitment, j, cells[j], proofs[j])
        for cells, proofs, commitment in sets
        for j in range(n_columns)
    ]
    n_cells = len(items)
    _partial(stage="built", cells=n_cells)

    spans = ("da_verify", "da_derive", "da_msm", "da_pairing")
    before = _span_totals(spans)

    def batched_run():
        assert verify_cell_kzg_proof_batch(items, kzg)

    t = _trials(batched_run, n=3)
    stages = _span_deltas(before, _span_totals(spans))

    # same-run control: the per-cell scalar oracle on an evenly spaced
    # subsample, extrapolated to cells/sec
    sub = items[:: max(1, n_cells // oracle_n)][:oracle_n]

    def oracle_run():
        for c, j, cell, proof in sub:
            assert verify_cell_kzg_proof(c, j, cell, proof, kzg)

    tr = _trials(oracle_run, n=2, label="control")

    batched_cps = n_cells / t["median_s"]
    oracle_cps = len(sub) / tr["median_s"]
    speedup = batched_cps / oracle_cps
    floor = 1.5 if SMOKE else 5.0
    assert speedup >= floor, (
        f"batched cell verification only {speedup:.2f}x the scalar "
        f"oracle (floor {floor}x)"
    )

    # verdict parity on a tampered set: batch refuses, oracle pinpoints
    ci, jj, cell, proof = items[n_cells // 2]
    bad = bytearray(cell)
    bad[0] ^= 1
    bad_items = list(items)
    bad_items[n_cells // 2] = (ci, jj, bytes(bad), proof)
    assert not verify_cell_kzg_proof_batch(bad_items, kzg)
    assert not verify_cell_kzg_proof(ci, jj, bytes(bad), proof, kzg)
    assert verify_cell_kzg_proof(*items[0][:2], items[0][2], items[0][3], kzg)

    return {
        "metric": "da_verify",
        "value": round(batched_cps, 1),
        "unit": "cells/s (batched RLC lane)",
        "vs_baseline": round(speedup, 2),
        "baseline_control": (
            f"per-cell scalar oracle, {len(sub)}-cell same-run subsample"
        ),
        "config": {
            "blobs": n_blobs,
            "domain": n_domain,
            "columns": n_columns,
            "cells": n_cells,
            "oracle_cells_per_s": round(oracle_cps, 1),
            "tamper_parity": "passed",
        },
        "stages": stages,
        "spread": t,
        "control_spread": tr,
    }


def bench_da_withholding(jax):
    """The DA withholding-recovery scenario as a first-class bench entry
    (testing/testnet.run_column_withholding_scenario): an adversary
    proposes blob blocks while suppressing erasure-coded columns at
    publish AND over RPC. Sub-50% kept — every honest node's sampling
    fails, the fleet refuses the head and finalizes past it; >=50% kept —
    honest nodes hit the reconstruction threshold, promote to full
    availability, and import. Headline: wall seconds from the recovery
    proposal's heal to finality (the soak-recovery shape); refusal/
    reconstruction counts ride along. The chain-health oracle asserts
    single-head + finality between phases."""
    from dataclasses import replace

    from lighthouse_tpu.testing.testnet import (
        DasTestnetEthSpec,
        run_column_withholding_scenario,
    )
    from lighthouse_tpu.types.chain_spec import minimal_spec

    spec = replace(
        minimal_spec(),
        altair_fork_epoch=0,
        bellatrix_fork_epoch=0,
        capella_fork_epoch=0,
        deneb_fork_epoch=0,
    )
    t0 = time.perf_counter()
    report = run_column_withholding_scenario(
        spec, DasTestnetEthSpec, seed=2026
    )
    total_s = time.perf_counter() - t0
    return {
        "metric": "da_withholding",
        "value": report["recovery_to_finality_s"],
        "unit": "s heal->finality (after >=50% recovery import)",
        "vs_baseline": None,
        "baseline_control": "chain-health oracle invariants (pass/fail)",
        "config": {
            "withheld_refusal": len(report["withheld_refusal"]),
            "sampling_failures": report["sampling_failures"],
            "reconstructions": report["reconstructions"],
            "refusal_recovery_slots": report["refusal_recovery_slots"],
            "recovery_slots": report["recovery_slots"],
            "head_convergence_s": report["head_convergence_s"],
            "scenario_wall_s": round(total_s, 1),
            "seed": report["seed"],
        },
    }


def bench_block_import(jax):
    """North-star metric 5 at harness scale. Runs under whichever BLS
    backend `--bls-backend`/BENCH_BLS_BACKEND selects (default host;
    `tpu` exercises the device verifier the node actually wires), and
    attaches a per-stage span breakdown from the tracing histograms —
    signature_batch_verify is nested inside state_transition, so stages
    overlap rather than sum to the total."""
    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec

    backend = os.environ.get("BENCH_BLS_BACKEND", "host")
    bls.set_backend(backend)
    h = BeaconChainHarness(minimal_spec(), MinimalEthSpec, validator_count=64)

    _STAGES = (
        "signature_batch_verify",
        "signature_set_assembly",
        "bls_rlc_accumulate",
        "bls_hash_to_g2",
        "bls_msm_g2",
        "bls_pairing",
        "bls_parallel_pairing",
        "state_transition",
        "fork_choice_on_block",
    )
    before = _span_totals(_STAGES)
    times = []
    for _ in range(8):
        slot = h.chain.head_state.slot + 1
        h.slot_clock.set_slot(slot)
        t0 = time.perf_counter()
        h.add_block_at_slot(slot)
        times.append(time.perf_counter() - t0)
        h.attest_to_head(slot)
    stages = _span_deltas(before, _span_totals(_STAGES))
    from lighthouse_tpu.crypto.bls import cache_stats

    return {
        "metric": "block_import_ms",
        "value": round(statistics.median(times) * 1000, 2),
        "unit": "ms/block (produce+sign+import)",
        "config": {
            "validators": 64,
            "spec": "minimal",
            "blocks": len(times),
            "backend": backend,
        },
        "stages": stages,
        "cache": cache_stats(),
    }


def bench_block_production(jax):
    """Proposer pipeline (north-star 5 at registry scale): unsigned-block
    production at 1M validators across an epoch boundary, cold (the
    advance to the proposal slot — an epoch transition — paid inline on
    the hot path) vs pre-advanced (the StateAdvanceTimer already built
    the boundary state off-path; production starts from the cached CoW
    snapshot). Stage means come from the `block_production` trace-root
    histograms: `advance` collapses in the pre-advanced runs while
    `pack`/`assemble` are invariant."""
    import gc

    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.beacon_chain.state_advance import StateAdvanceTimer
    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.state_processing import per_slot_processing

    n = 5_000 if SMOKE else 1_000_000
    # the boundary-ready 1M Altair fixture the epoch bench uses:
    # randomized participation/scores one slot shy of a REAL epoch
    # boundary (3*SPE-1 — justification and rewards run in full, unlike
    # the skipped-work genesis boundary)
    st, spec, E = _build_epoch_state(n, resident=True)
    # the cloned-registry fixture keeps only validator 0's pubkey: re-seat
    # the sync committees from the cloned registry so the assemble stage's
    # sync-aggregate processing resolves every committee pubkey
    from lighthouse_tpu.state_processing.altair import get_next_sync_committee

    sc = get_next_sync_committee(st, E)
    st.current_sync_committee = sc
    st.next_sync_committee = sc.copy()
    st.hash_tree_root()  # commit caches: trials measure increments
    h = BeaconChainHarness(spec, E, validator_count=8)
    chain = h.chain
    # graft the fixture in as the head's state: production reads the
    # parent state by root, so the head root must be the fixture's own
    # header root (what process_block_header will check parent against)
    tmp = st.copy()
    per_slot_processing(tmp, spec, E)  # untimed: fills the header's state root
    parent_root = tmp.latest_block_header.hash_tree_root()
    del tmp
    gc.collect()
    chain.head_root = parent_root
    chain._states[parent_root] = st
    slot = int(st.slot) + 1
    h.slot_clock.set_slot(slot)
    reveal = b"\x5c" * 96  # NO_VERIFICATION production: any 96 bytes

    _STAGES = ("block_production", "advance", "pack", "assemble")

    def cold():
        chain.state_advance_cache.clear()
        chain.produce_block_on_state(slot, reveal)

    cold()  # untimed warmup: one-time caches (pubkey hints, shuffling)
    gc.collect()
    before = _span_totals(_STAGES)
    t_cold = _trials(cold, n=3, label="cold_trial", between=gc.collect)
    cold_stages = _span_deltas(before, _span_totals(_STAGES))

    timer = StateAdvanceTimer(chain)
    chain.state_advance_cache.clear()
    timer._advance(slot - 1)  # the slot-tail pre-advance, off the timed path
    hits = REGISTRY.counter("state_advance_hits_total").value()

    def pre_advanced():
        chain.produce_block_on_state(slot, reveal)

    before = _span_totals(_STAGES)
    t_pre = _trials(pre_advanced, n=3, label="pre_advanced_trial",
                    between=gc.collect)
    pre_stages = _span_deltas(before, _span_totals(_STAGES))
    assert REGISTRY.counter("state_advance_hits_total").value() > hits

    speedup = t_cold["median_s"] / t_pre["median_s"]
    if not SMOKE:
        # acceptance: the pre-advance absorbs the boundary transition
        assert speedup >= 5.0, (
            f"pre-advanced production only {speedup:.1f}x faster than cold"
        )
    return {
        "metric": "block_production_ms",
        "value": round(t_pre["median_s"] * 1000, 2),
        "unit": "ms/block (pre-advanced, epoch boundary, 1M validators)",
        "config": {
            "validators": n,
            "spec": "minimal",
            "slot": slot,
            "trials": 3,
        },
        "details": {
            "cold_ms": round(t_cold["median_s"] * 1000, 2),
            "pre_advanced_ms": round(t_pre["median_s"] * 1000, 2),
            "speedup": round(speedup, 2),
            "cold_stages": cold_stages,
            "pre_advanced_stages": pre_stages,
        },
        "spread": t_pre,
        "control_spread": t_cold,
    }


def _build_1m_state(n: int):
    """The shared 1M-registry fixture: interop genesis + cloned registry,
    converted to the node's tree-states representation."""
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.chain import _make_persistent
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    state = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    v0 = state.validators[0]
    vs, bal = [], []
    for i in range(n):
        v = v0.copy()
        v.withdrawal_credentials = i.to_bytes(32, "little")
        vs.append(v)
        bal.append(32_000_000_000)
    state.validators = vs
    state.balances = bal
    # the node's tree-states representation: structurally-shared registry
    # (PersistentContainerList) + balance blocks + resident columns —
    # what block import uses (the node attaches columns at its first
    # epoch transition; re-roots then serve element roots from them)
    _make_persistent(state)
    from lighthouse_tpu.state_processing.registry_columns import (
        registry_columns_for,
    )

    registry_columns_for(state).refresh(state)
    return state, vs


def bench_state_root(jax):
    """North-star metric 2: `hash_tree_root` of a BeaconState at 1M
    validators — the per-slot incremental update (a block's worth of
    mutations re-rooted through the dirty-index caches), with the cold
    columnar full-build promoted to a first-class number (median + spread
    over fresh-cache rebuilds). Control = this state's root via the plain
    non-cached recompute path."""
    import random as _r

    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
    from lighthouse_tpu.utils.sha256_batch import batch_mode

    n = 5_000 if SMOKE else 1_000_000
    state, vs = _build_1m_state(n)
    rng = _r.Random(11)

    # cold build: fresh state-level cache each trial (the registry's
    # columnar batched pass end to end — no memos, no committed layers)
    def cold_build():
        state.__dict__.pop("_thc_cache", None)
        return state.hash_tree_root()

    t_cold = _trials(cold_build, n=3, label="cold_trial")
    root = state.hash_tree_root()

    t_copy0 = time.perf_counter()
    state_copy = state.copy()  # O(#blocks) structural share + CoW layers
    copy_s = time.perf_counter() - t_copy0
    assert state_copy.hash_tree_root() == root

    def mutate_and_root():
        # a block's worth of churn: ~128 attesting balance changes + a
        # couple of validator-record updates (CoW mutation discipline)
        for _ in range(128):
            i = rng.randrange(n)
            state.balances[i] = int(state.balances[i]) + 1
        for _ in range(2):
            v = state.validators.mutate(rng.randrange(n))
            v.effective_balance = int(v.effective_balance) + 1
        return state.hash_tree_root()

    mutate_and_root()  # first update un-shares the CoW layers once
    t = _trials(mutate_and_root, n=5)

    # control: the same state through the NON-cached recompute path,
    # measured on a 1/64 slice and extrapolated (a full recompute at 1M
    # is minutes — exactly the point of the cache)
    from lighthouse_tpu.ssz.core import List as SszList
    from lighthouse_tpu.types.containers import build_types

    ctrl_cls = SszList[build_types(E).Validator, E.VALIDATOR_REGISTRY_LIMIT]
    ctrl_slice = vs[: max(1, n // 64)]
    ctrl_cls.hash_tree_root_of(ctrl_slice)  # warm-up: exclude compiles
    t_ctrl = _trials(lambda: ctrl_cls.hash_tree_root_of(ctrl_slice), n=1)
    control_s = t_ctrl["median_s"] * 64

    return {
        "metric": "state_root_update_1m",
        "value": round(t["median_s"] * 1000, 2),
        "unit": "ms/update (128-balance + 2-validator churn, re-root)",
        "vs_baseline": round(control_s / t["median_s"], 2),
        "baseline_control": "non-cached registry recompute (1/64 slice x64)",
        "cold_build": {
            "value": round(t_cold["median_s"], 2),
            "unit": "s/cold columnar build",
            "spread": t_cold,
        },
        "config": {
            "validators": n,
            "cold_build_s": round(t_cold["median_s"], 2),
            "state_copy_ms": round(copy_s * 1000, 2),
            "sha256_batch_mode": batch_mode(),
        },
        "spread": t,
    }


def bench_epoch_reroot(jax):
    """Epoch-boundary re-root at 1M validators: the effective-balance
    sweep dirties ~a third of the registry. Since PR 6 the container
    list's dirty cap (1<<20) keeps the index set exact at this scale, so
    the re-root is a 333k-row sparse update whose element roots come
    straight from the resident columns — no Python object extraction
    (the r05 path overflowed to a full 7M-hash columnar rebuild:
    14.7 s)."""
    n = 5_000 if SMOKE else 1_000_000
    state, _ = _build_1m_state(n)
    state.hash_tree_root()  # commit the caches (cold build)
    eb = [31_000_000_000, 32_000_000_000]

    def churn_and_reroot():
        # mass effective-balance churn: every 3rd validator flips
        for i in range(0, n, 3):
            v = state.validators.mutate(i)
            v.effective_balance = eb[0]
        eb.reverse()
        return state.hash_tree_root()

    t = _trials(churn_and_reroot, n=2)
    return {
        "metric": "epoch_boundary_reroot_1m",
        "value": round(t["median_s"], 2),
        "unit": "s/re-root (n/3 effective-balance churn, sparse columnar path)",
        "config": {"validators": n, "churned": (n + 2) // 3},
        "spread": t,
    }


def _build_epoch_state(n: int, resident: bool):
    """A boundary-ready Altair state of `n` cloned validators with
    randomized participation/scores and steady-state balances (inside
    the hysteresis band: real epochs move balances by rewards, not by
    mass effective-balance churn). `resident` converts to the node's
    tree-states representation and pre-warms the columns (the one-time
    cold build the bench excludes, exactly like the hash caches')."""
    import random as _r
    from dataclasses import replace

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    base = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    # clone validator 0 out to n (deposit-path construction of n keys is
    # minutes of BLS; registry shape is what the sweep cares about)
    rng = _r.Random(3)
    v0 = base.validators[0]
    vs, bal, prev, cur, scores = [], [], bytearray(n), bytearray(n), []
    for i in range(n):
        v = v0.copy()
        v.withdrawal_credentials = i.to_bytes(32, "little")
        vs.append(v)
        # inside the hysteresis band around the 32-ETH effective balance
        bal.append(32_000_000_000 + rng.randrange(1_000_000_000))
        prev[i] = rng.randrange(8)
        cur[i] = rng.randrange(8)
        scores.append(rng.randrange(4))
    base.validators = vs
    base.balances = bal
    base.previous_epoch_participation = prev
    base.current_epoch_participation = cur
    base.inactivity_scores = scores
    base.slot = 3 * E.SLOTS_PER_EPOCH - 1
    if resident:
        from lighthouse_tpu.beacon_chain.chain import _make_persistent
        from lighthouse_tpu.state_processing.registry_columns import (
            registry_columns_for,
        )

        _make_persistent(base)
        cols = registry_columns_for(base)
        if cols is not None:  # None under LIGHTHOUSE_TPU_RESIDENT_COLUMNS=0
            cols.refresh(base)
    return base, spec, E


_EPOCH_STAGE_SPANS = tuple(
    f"epoch_stage_{s}"
    for s in (
        "columns_refresh",
        "justification",
        "inactivity",
        "rewards",
        "registry_updates",
        "slashings",
        "effective_balances",
        "final_updates",
    )
)


def _epoch_metric(jax, n: int, metric: str, trials: int, control_trials: int,
                  control_fraction: int):
    """Shared body of the epoch-transition metrics: resident-columns
    trials with a per-stage span breakdown and a zero-rebuild check,
    plus a same-run per-validator-oracle control
    (state_processing/epoch_reference.process_epoch_reference — the
    retained scalar spec-loop implementation, bit-identical by the
    differential suite) on a 1/`control_fraction` subsample, scaled.
    The r05 legacy snapshot path is also timed once on the subsample
    (`legacy_snapshot` in the JSON) for metric continuity."""
    import gc

    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.state_processing.epoch_reference import (
        process_epoch_reference,
    )
    from lighthouse_tpu.state_processing.per_epoch import process_epoch

    # the metric MEASURES residency: neutralize an inherited process-wide
    # opt-out for the trial phases, restoring it afterwards (the legacy
    # continuity timing below sets it explicitly either way)
    prior_resident = os.environ.pop("LIGHTHOUSE_TPU_RESIDENT_COLUMNS", None)

    state, spec, E = _build_epoch_state(n, resident=True)
    copies = [state.copy() for _ in range(trials)]

    def rebuild_counts():
        c = REGISTRY.counter("registry_columns_rebuilds_total")
        return {k[0][1]: v for k, v in c.values().items()}

    before_rebuilds = rebuild_counts()
    spans_before = _span_totals(_EPOCH_STAGE_SPANS)

    def run():
        process_epoch(copies.pop(), spec, E)  # copy cost excluded

    # gc BETWEEN trials (untimed): the consumed state must not skew the
    # next trial, but a full-heap collection over a 1M-object registry is
    # not epoch-transition time
    t = _trials(run, n=trials, between=gc.collect)
    stages = _span_deltas(spans_before, _span_totals(_EPOCH_STAGE_SPANS))
    rebuild_delta = {
        k: v - before_rebuilds.get(k, 0) for k, v in rebuild_counts().items()
    }
    del state, copies
    gc.collect()

    # same-run per-validator-oracle control on a plain-list subsample
    # (the oracle is representation-agnostic scalar Python; plain lists
    # keep it free of any machinery under test)
    ctrl_n = max(1000, n // control_fraction)
    ctrl_state, ctrl_spec, _ = _build_epoch_state(ctrl_n, resident=False)
    ctrl_copies = [ctrl_state.copy() for _ in range(control_trials)]

    def ctrl_run():
        process_epoch_reference(ctrl_copies.pop(), ctrl_spec, E)

    th = _trials(
        ctrl_run, n=control_trials, label="control_trial", between=gc.collect
    )
    control_s = th["median_s"] * (n / ctrl_n)

    # continuity: the r05 legacy snapshot path (vectorized over
    # per-epoch object snapshots), one timing on the same subsample
    os.environ["LIGHTHOUSE_TPU_RESIDENT_COLUMNS"] = "0"
    try:
        legacy_state, legacy_spec, _ = _build_epoch_state(
            ctrl_n, resident=True
        )
        t0 = time.perf_counter()
        process_epoch(legacy_state, legacy_spec, E)
        legacy_s = time.perf_counter() - t0
    finally:
        if prior_resident is None:
            del os.environ["LIGHTHOUSE_TPU_RESIDENT_COLUMNS"]
        else:
            os.environ["LIGHTHOUSE_TPU_RESIDENT_COLUMNS"] = prior_resident
    del ctrl_state, ctrl_copies, legacy_state
    gc.collect()

    return {
        "metric": metric,
        "value": round(t["median_s"] * 1000, 1),
        "unit": f"ms/epoch ({n} validators, minimal preset)",
        "vs_baseline": round(control_s / t["median_s"], 3),
        "baseline_control": (
            "per-validator oracle (epoch_reference.process_epoch_reference, "
            f"scalar spec loops) on a 1/{control_fraction} subsample "
            f"x{control_fraction}, same run"
        ),
        "config": {
            "validators": n,
            "control_validators": ctrl_n,
            "steady_state_column_rebuilds": rebuild_delta,
            "legacy_snapshot_subsample_ms": round(legacy_s * 1000, 1),
            "legacy_snapshot_scaled_ms": round(
                legacy_s * (n / ctrl_n) * 1000, 1
            ),
        },
        "stages": stages,
        "spread": t,
        "control_spread": th,
    }


def bench_epoch_transition(jax):
    """Altair epoch sweep at 100k validators over the resident columnar
    registry (kept alongside epoch_transition_1m for vs_baseline
    history; r01-r05 measured the legacy snapshot path on plain lists —
    see BENCH_NOTES.md for the continuity note)."""
    n = 2_000 if SMOKE else 100_000
    return _epoch_metric(
        jax, n, "epoch_transition_100k", trials=3, control_trials=3,
        control_fraction=8,
    )


def bench_epoch_transition_1m(jax):
    """THE tentpole metric: full epoch transition at 1M validators on
    the state-resident columnar registry — zero column rebuilds in
    steady state (counter-asserted in the JSON), every sweep an array
    program, writebacks as vectorized diffs."""
    n = 20_000 if SMOKE else 1_000_000
    return _epoch_metric(
        jax, n, "epoch_transition_1m", trials=3, control_trials=3,
        control_fraction=16,
    )


def _build_attestation_block(n: int, atts_per_committee: int):
    """A mainnet-shaped attestation batch: an altair tree-states state of
    `n` cloned validators (committee size ≈ mainnet's ~450 at 1M) plus a
    block's worth of valid previous-epoch attestations — every
    (slot, committee) pair of the previous epoch × `atts_per_committee`
    random aggregation patterns (the duplicate-attester fold is
    exercised, exactly like real aggregates)."""
    import random as _r

    from lighthouse_tpu.state_processing.accessors import (
        committee_cache_at,
        get_previous_epoch,
    )
    from lighthouse_tpu.types.containers import build_types
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    state, spec, _ = _build_epoch_state(n, resident=True)
    state.slot = int(state.slot) + 1  # epoch start: all delays includable
    t = build_types(E)
    rng = _r.Random(11)
    prev = get_previous_epoch(state, E)
    cc = committee_cache_at(state, prev, E)
    atts = []
    for slot in range(prev * E.SLOTS_PER_EPOCH, (prev + 1) * E.SLOTS_PER_EPOCH):
        for index in range(cc.committees_per_slot):
            committee = cc.committee_array(slot, index)
            for _ in range(atts_per_committee):
                bits = [rng.random() < 0.7 for _ in range(committee.size)]
                if not any(bits):
                    bits[0] = True
                atts.append(
                    t.Attestation(
                        aggregation_bits=bits,
                        data=t.AttestationData(
                            slot=slot,
                            index=index,
                            beacon_block_root=state.block_roots[
                                slot % E.SLOTS_PER_HISTORICAL_ROOT
                            ],
                            source=state.previous_justified_checkpoint,
                            target=t.Checkpoint(
                                epoch=prev,
                                root=state.block_roots[
                                    (prev * E.SLOTS_PER_EPOCH)
                                    % E.SLOTS_PER_HISTORICAL_ROOT
                                ],
                            ),
                        ),
                        signature=b"\x00" * 96,
                    )
                )
    return state, spec, atts


def bench_attestation_batch(jax):
    """The block-import hot path PRs 3-6 never touched: apply a block's
    worth of attestations (participation scatter + proposer rewards).
    Columnar pipeline (attestation_batch.process_attestations) vs the
    retained scalar oracle (process_attestations_reference), same
    attestations, fresh state copies, same run."""
    import gc

    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.state_processing.attestation_batch import (
        process_attestations,
        process_attestations_reference,
    )
    from lighthouse_tpu.state_processing.per_block import ConsensusContext
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    n = 2_000 if SMOKE else 16_384  # committee ≈ 512 ≈ mainnet shape
    per_committee = 1 if SMOKE else 4  # 8 slots × 4 committees × 4 = 128
    state, spec, atts = _build_attestation_block(n, per_committee)
    from lighthouse_tpu.state_processing.accessors import (
        committee_cache_at,
        get_previous_epoch,
    )
    from lighthouse_tpu.types.containers import build_types

    fork = build_types(E).fork_of_state(state)
    proposer = 0

    def fresh_ctxt():
        ctxt = ConsensusContext(state.slot)
        ctxt.set_proposer_index(proposer)
        return ctxt

    def warm(s):
        # a node imports blocks against states whose epoch shuffling is
        # already cached; pre-build it (both paths get the same warmup)
        committee_cache_at(s, get_previous_epoch(s, E), E)
        return s

    trials = 3
    copies = [warm(state.copy()) for _ in range(trials + 1)]

    def run():
        process_attestations(
            copies.pop(), atts, spec, E, False, fresh_ctxt(), fork
        )

    before = REGISTRY.counter("attestation_batch_total").values().copy()
    spans_before = _span_totals(("attestation_apply",))
    t = _trials(run, n=trials, between=gc.collect)
    stages = _span_deltas(spans_before, _span_totals(("attestation_apply",)))
    after = REGISTRY.counter("attestation_batch_total").values()

    # differential check rides the bench: batched and scalar end states
    # must agree bit-for-bit on participation and balances
    batched = copies.pop()
    process_attestations(batched, atts, spec, E, False, fresh_ctxt(), fork)
    oracle = warm(state.copy())
    ctrl_times = []
    for i in range(2):
        ctrl_state = oracle if i == 0 else warm(state.copy())
        t0 = time.perf_counter()
        process_attestations_reference(
            ctrl_state, atts, spec, E, False, fresh_ctxt(), fork
        )
        ctrl_times.append(time.perf_counter() - t0)
        _partial(control_trial=i + 1, of=2, s=round(ctrl_times[-1], 4))
    assert bytes(batched.previous_epoch_participation) == bytes(
        oracle.previous_epoch_participation
    ), "batched vs scalar participation mismatch"
    assert list(batched.balances) == list(oracle.balances), (
        "batched vs scalar proposer reward mismatch"
    )

    ctrl = statistics.median(ctrl_times)
    return {
        "metric": "attestation_batch_ms",
        "value": round(t["median_s"] * 1000, 2),
        "unit": f"ms/block ({len(atts)} attestations, {n} validators)",
        "vs_baseline": round(ctrl / t["median_s"], 2),
        "baseline_control": (
            "retained scalar loop (process_attestations_reference), same "
            "attestations + fresh state copies, same run"
        ),
        "config": {
            "validators": n,
            "attestations": len(atts),
            "scalar_ms": round(ctrl * 1000, 2),
            "differential_check": "passed",
            "path_counters": {
                k[0][1]: v - before.get(k, 0) for k, v in after.items()
            },
        },
        "stages": stages,
        "spread": t,
    }


def _hist_percentiles(buckets, counts, qs=(0.5, 0.9, 0.99)):
    """Approximate quantiles from cumulative histogram buckets (linear
    interpolation inside the landing bucket; the +Inf bucket reports the
    last finite bound). `counts` is per-bucket (non-cumulative)."""
    total = sum(counts)
    if total == 0:
        return None
    out = {}
    for q in qs:
        target = q * total
        cum = 0.0
        value = buckets[-1]
        for i, c in enumerate(counts):
            prev_cum = cum
            cum += c
            if cum >= target:
                lo = 0.0 if i == 0 else buckets[i - 1]
                hi = buckets[i] if i < len(buckets) else buckets[-1]
                frac = (target - prev_cum) / c if c else 1.0
                value = lo + (hi - lo) * frac
                break
        out[f"p{int(q * 100)}_ms"] = round(value * 1000.0, 3)
    out["count"] = total
    return out


def _hist_snapshot(prefix: str):
    """Per-WorkType (buckets, counts) of one beacon_processor histogram
    family — PR 9's queue observability, consumed as before/after deltas
    so a bench reports only ITS OWN traffic."""
    from lighthouse_tpu.beacon_processor import WorkType
    from lighthouse_tpu.metrics import REGISTRY

    out = {}
    for t in WorkType:
        kind = t.name.lower()
        buckets, counts, _total, _sum = REGISTRY.histogram(
            prefix + kind
        ).snapshot()
        out[kind] = (buckets, counts)
    return out


def _queue_wait_snapshot():
    """Time-in-queue (submit → worker pickup) per WorkType."""
    return _hist_snapshot("beacon_processor_queue_wait_seconds_")


def _queue_wait_percentiles(before, after):
    """kind -> {p50_ms, p90_ms, p99_ms, count} for every WorkType whose
    queue saw traffic between the two snapshots."""
    out = {}
    for kind, (buckets, counts) in after.items():
        b_counts = before.get(kind, (buckets, [0] * len(counts)))[1]
        delta = [a - b for a, b in zip(counts, b_counts)]
        p = _hist_percentiles(buckets, delta)
        if p is not None:
            out[kind] = p
    return out


def bench_sync_catchup(jax):
    """Sync-engine catch-up rate: blocks/sec for a fresh node pulling N
    slots from a loopback peer through the batch state machine
    (network/sync/range_sync), with the old sequential single-peer loop
    (`sequential_sync_with`, retained in-tree) as the same-run
    vs_baseline control. The range-sync retry/failure counters ride
    along in the JSON so a fault-free run proves itself fault-free —
    and a faulty one shows its retries."""
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.network import NetworkService
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")  # measures the sync engine, not BLS
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    slots = 2 * E.SLOTS_PER_EPOCH if SMOKE else 8 * E.SLOTS_PER_EPOCH
    serve = BeaconChainHarness(spec, E, validator_count=16)
    serve.extend_chain(slots, attest=False)
    na = NetworkService(serve.chain, heartbeat_interval=None).start()

    def one_catchup(method):
        b = BeaconChainHarness(spec, E, validator_count=16)
        nb = NetworkService(b.chain, heartbeat_interval=None).start()
        try:
            b.slot_clock.set_slot(serve.chain.head_state.slot)
            peer = nb.connect("127.0.0.1", na.port)
            t0 = time.perf_counter()
            imported = getattr(nb.sync, method)(peer)
            dt = time.perf_counter() - t0
            assert imported == slots, f"{method} imported {imported}/{slots}"
            return dt
        finally:
            nb.stop()

    def counters():
        return {
            name: REGISTRY.counter(name).value(chain="range")
            for name in (
                "sync_batch_downloads_total",
                "sync_batch_retries_total",
                "sync_batch_failures_total",
            )
        }

    def spread(samples):
        return {
            "median_s": statistics.median(samples),
            "min_s": min(samples),
            "max_s": max(samples),
            "trials": len(samples),
        }

    before = counters()
    queue_before = _queue_wait_snapshot()
    engine, serial = [], []
    for i in range(3):
        engine.append(one_catchup("sync_with"))
        _partial(trial=i + 1, of=3, s=round(engine[-1], 4))
    after = counters()
    queue_wait = _queue_wait_percentiles(queue_before, _queue_wait_snapshot())
    for i in range(3):
        serial.append(one_catchup("sequential_sync_with"))
        _partial(control_trial=i + 1, of=3, s=round(serial[-1], 4))
    na.stop()
    med = statistics.median(engine)
    med_serial = statistics.median(serial)
    return {
        "metric": "sync_catchup",
        "value": round(slots / med, 1),
        "unit": "blocks/sec (two-node loopback catch-up, batch state machine)",
        "vs_baseline": round(med_serial / med, 3),
        "baseline_control": "pre-engine sequential single-peer sync loop, same run",
        "config": {"slots": slots, "validators": 16, "spec": "minimal"},
        "counters": {k: after[k] - before[k] for k in after},
        # PR 9 queue observability: time-in-queue percentiles per WorkType
        # across the engine trials (chain_segment is the sync lane) — the
        # backpressure number the blocks/sec headline can't show
        "queue_wait": queue_wait,
        "spread": spread(engine),
        "control_spread": spread(serial),
    }


def _work_run_snapshot():
    """Handler wall time per WorkType — the import-latency side of the
    queue story."""
    return _hist_snapshot("beacon_processor_work_seconds_")


def bench_gossip_soak(jax):
    """Event-driven node under storm: N faulty peers sustain an
    attestation + aggregate flood (decodable, unknown-root — the worst
    honest-looking spam) at a fresh node WHILE it range-syncs the full
    chain from an honest peer. Headline: catch-up blocks/sec under
    flood; vs_baseline is the fraction of the same run's flood-free
    catch-up rate retained (1.0 = the flood cost nothing). The JSON
    carries the robustness evidence: drop counts (processor backpressure
    + reprocess caps — shed, not hung), queue-wait AND handler-run
    percentiles per WorkType lane, and the reprocess counters."""
    import threading
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.network import NetworkService
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")  # measures the pipeline, not BLS
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    slots = 2 * E.SLOTS_PER_EPOCH if SMOKE else 4 * E.SLOTS_PER_EPOCH
    flooders = 2
    serve = BeaconChainHarness(spec, E, validator_count=16)
    serve.extend_chain(slots, attest=False)
    na = NetworkService(serve.chain, heartbeat_interval=None).start()
    tip = serve.chain.head_state.slot
    template = serve.make_unaggregated_attestations(
        tip, serve.chain.head_root
    )[0]
    t = serve.chain.types
    garbage_roots = [bytes([0x70 + j]) * 32 for j in range(8)]

    def one_catchup(flood: bool):
        b = BeaconChainHarness(spec, E, validator_count=16)
        nb = NetworkService(b.chain, heartbeat_interval=None).start()
        nfs = []
        stop_flood = threading.Event()
        sent = [0]

        def flood_loop(nf, lane):
            i = 0
            while not stop_flood.is_set():
                att = template.copy()
                att.data.beacon_block_root = garbage_roots[
                    i % len(garbage_roots)
                ]
                att.signature = (lane * (1 << 40) + i).to_bytes(
                    8, "little"
                ) + bytes(88)
                if i % 4 == 3:
                    agg = t.SignedAggregateAndProof(
                        message=t.AggregateAndProof(
                            aggregator_index=0,
                            aggregate=att,
                            selection_proof=b"\x01" * 96,
                        ),
                        signature=b"\x02" * 96,
                    )
                    nf.gossip.publish(nf.topic_aggregate, agg.serialize())
                else:
                    nf.gossip.publish(
                        nf.topic_att, t.Attestation.serialize_value(att)
                    )
                sent[0] += 1
                i += 1
                time.sleep(0.001)  # sustained flood, not a GIL vice

        threads = []
        try:
            b.slot_clock.set_slot(tip)
            peer = nb.connect("127.0.0.1", na.port)
            if flood:
                for lane in range(flooders):
                    h = BeaconChainHarness(spec, E, validator_count=16)
                    nf = NetworkService(h.chain, heartbeat_interval=None).start()
                    nf.connect("127.0.0.1", nb.port)
                    nfs.append(nf)
                threads = [
                    threading.Thread(
                        target=flood_loop, args=(nf, lane), daemon=True
                    )
                    for lane, nf in enumerate(nfs)
                ]
                for th in threads:
                    th.start()
            t0 = time.perf_counter()
            imported = nb.sync.sync_with(peer)
            dt = time.perf_counter() - t0
            assert imported == slots, f"imported {imported}/{slots}"
            return dt, sent[0]
        finally:
            stop_flood.set()
            for th in threads:
                th.join(timeout=5)
            for nf in nfs:
                nf.stop()
            nb.stop()

    def counters():
        out = {}
        for name, labels in (
            ("reprocess_held_total", {}),
            ("reprocess_drained_total", {}),
            ("reprocess_expired_total", {"reason": "root_cap"}),
            ("reprocess_expired_total", {"reason": "total_cap"}),
            ("reprocess_expired_total", {"reason": "shutdown"}),
            ("gossip_ignored_total", {}),
            ("gossip_internal_error_total", {}),
        ):
            key = name + (
                f"[{next(iter(labels.values()))}]" if labels else ""
            )
            out[key] = REGISTRY.counter(name).value(**labels)
        for kind in ("gossip_attestation", "gossip_aggregate"):
            out[f"dropped[{kind}]"] = REGISTRY.counter(
                "beacon_processor_dropped_total"
            ).value(kind=kind)
        return out

    def spread(samples):
        return {
            "median_s": statistics.median(samples),
            "min_s": min(samples),
            "max_s": max(samples),
            "trials": len(samples),
        }

    before = counters()
    qw_before, run_before = _queue_wait_snapshot(), _work_run_snapshot()
    flood_times, flood_sent = [], 0
    try:
        for i in range(3):
            dt, sent = one_catchup(flood=True)
            flood_times.append(dt)
            flood_sent += sent
            _partial(trial=i + 1, of=3, s=round(dt, 4), flood_msgs=sent)
        after = counters()
        queue_wait = _queue_wait_percentiles(qw_before, _queue_wait_snapshot())
        handler_run = _queue_wait_percentiles(run_before, _work_run_snapshot())
        clean_times = []
        for i in range(3):
            dt, _ = one_catchup(flood=False)
            clean_times.append(dt)
            _partial(control_trial=i + 1, of=3, s=round(dt, 4))
    finally:
        # a failed trial must not leak the serve node's server/worker
        # threads into the rest of the bench process
        na.stop()
    med, med_clean = statistics.median(flood_times), statistics.median(clean_times)
    return {
        "metric": "gossip_soak",
        "value": round(slots / med, 1),
        "unit": (
            f"blocks/sec (range sync under attestation/aggregate flood, "
            f"{flooders} faulty peers)"
        ),
        # fraction of the flood-free catch-up rate retained under storm
        # (same run, same topology minus the flooders); 1.0 = free
        "vs_baseline": round(med_clean / med, 3),
        "baseline_control": "same-run flood-free catch-up (rate retained)",
        "config": {
            "slots": slots,
            "validators": 16,
            "spec": "minimal",
            "flooders": flooders,
            "flood_messages_total": flood_sent,
            "clean_blocks_per_sec": round(slots / med_clean, 1),
        },
        "counters": {k: round(after[k] - before[k], 1) for k in after},
        "queue_wait": queue_wait,
        "handler_run": handler_run,
        "spread": spread(flood_times),
        "control_spread": spread(clean_times),
    }


def bench_testnet_soak(jax):
    """Testnet soak: an N-node in-process fleet (real gossipsub/RPC/
    beacon_processor/SyncService per node, duties split across per-node
    VCs) runs healthy epochs, then takes scripted partition-heal cycles.
    Headline: slots finalized per wall-second across the healthy soak
    (per-epoch samples give the spread). The recovery story rides along:
    wall seconds from heal until every node shares one head
    (head_convergence_s) and until finality advances past the heal point
    (recovery_to_finality_s), one sample per cycle. The scenario oracle
    asserts invariants (single head, participation, zero internal
    errors) between phases — a soak that degrades silently fails loudly
    instead of reporting a pretty number."""
    from dataclasses import replace

    from lighthouse_tpu.testing.testnet import (
        ChainHealthOracle,
        Testnet,
        _finalized_epochs,
        _run_to_convergence,
    )
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    spec = replace(minimal_spec(), altair_fork_epoch=0)
    S = E.SLOTS_PER_EPOCH
    nodes = 3 if SMOKE else 5
    validators = 24 if SMOKE else 40
    soak_epochs = 3 if SMOKE else 5
    cycles = 1 if SMOKE else 2
    # BENCH_TESTNET_API_WORKERS=N boots every full node's Beacon API with
    # N forked serving workers (PR 18) — the A/B lever: a soak at 0 vs a
    # soak at 2 through --compare proves the serving tier doesn't tax the
    # chain's finalization rate
    api_workers = int(os.environ.get("BENCH_TESTNET_API_WORKERS", "0") or 0)
    net = Testnet.create(
        spec,
        E,
        node_count=nodes,
        validator_count=validators,
        seed=2026,
        api_workers=api_workers,
    )
    rates, recoveries, convergences, recovery_slots = [], [], [], []
    try:
        oracle = ChainHealthOracle(net)
        slot = 0
        fin_slots_prev = 0
        for ep in range(1, soak_epochs + 1):
            t0 = time.perf_counter()
            net.run_until_slot(ep * S, start_slot=slot + 1)
            slot = ep * S
            dt = time.perf_counter() - t0
            fin_slots = max(_finalized_epochs(net)) * S
            if fin_slots > fin_slots_prev:
                rates.append((fin_slots - fin_slots_prev) / dt)
                fin_slots_prev = fin_slots
            _partial(epoch=ep, finalized_slots=fin_slots, epoch_s=round(dt, 2))
        oracle.check(
            require_single_head=True,
            min_participation=0.9,
            what="healthy soak",
        )
        for cyc in range(cycles):
            names = [n.name for n in net.nodes]
            net.rng.shuffle(names)
            cut = nodes // 2 + 1
            net.partition(names[:cut], names[cut:])
            end = slot + S
            net.run_until_slot(end, start_slot=slot + 1)
            slot = end
            net.heal()
            rec = _run_to_convergence(net, oracle, start_slot=slot + 1)
            slot += rec["recovery_slots"]
            recoveries.append(rec["recovery_to_finality_s"])
            convergences.append(rec["head_convergence_s"])
            recovery_slots.append(rec["recovery_slots"])
            _partial(
                cycle=cyc + 1,
                of=cycles,
                recovery_to_finality_s=rec["recovery_to_finality_s"],
            )
        oracle.check(require_single_head=True, what="post-cycle fleet")
    finally:
        net.shutdown()

    def spread(samples):
        return {
            "median_s": round(statistics.median(samples), 3),
            "min_s": round(min(samples), 3),
            "max_s": round(max(samples), 3),
            "trials": len(samples),
        }

    from lighthouse_tpu.metrics import REGISTRY

    return {
        "metric": "testnet_soak",
        "value": round(statistics.median(rates), 2),
        "unit": (
            f"slots finalized per wall-second ({nodes}-node fleet, "
            f"healthy soak)"
        ),
        "config": {
            "nodes": nodes,
            "validators": validators,
            "soak_epochs": soak_epochs,
            "partition_heal_cycles": cycles,
            "api_workers": api_workers,
            "seed": net.seed,
            "spec": "minimal",
        },
        # the robustness headline: wall-clock to recover after a heal
        "recovery_to_finality": spread(recoveries),
        "head_convergence": spread(convergences),
        "recovery_slots": recovery_slots,
        "counters": {
            "faults_injected": sum(
                REGISTRY.counter("testnet_fault_injections_total").value(
                    kind=k
                )
                for k in ("partition", "heal")
            ),
            "frames_dropped": REGISTRY.counter(
                "testnet_gossip_frames_dropped_total"
            ).value(),
            "fork_backtracks": REGISTRY.counter(
                "sync_fork_backtracks_total"
            ).value(),
            "oracle_checks_passed": REGISTRY.counter(
                "scenario_invariant_checks_total"
            ).value(result="pass"),
        },
        "spread": {
            "median_rate": round(statistics.median(rates), 2),
            "min_rate": round(min(rates), 2),
            "max_rate": round(max(rates), 2),
            "samples": len(rates),
        },
    }


def bench_checkpoint_boot(jax):
    """Peer checkpoint sync: wall seconds from a bare store to a serving
    chain anchored on a live peer's finalized checkpoint — three HTTP
    round-trips (finality_checkpoints, state SSZ, block SSZ), two local
    tree-root verifications, and the chain boot. The backfill rate rides
    along as a sub-metric: blocks/s filling history backward over the
    RPC while the anchored chain serves forward."""
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.checkpoint_sync import checkpoint_boot
    from lighthouse_tpu.beacon_chain.harness import (
        HARNESS_GENESIS_TIME,
        BeaconChainHarness,
    )
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.http_api import HttpApiServer
    from lighthouse_tpu.network import NetworkService
    from lighthouse_tpu.store import HotColdDB, MemoryStore
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
    from lighthouse_tpu.utils.slot_clock import ManualSlotClock

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    S = E.SLOTS_PER_EPOCH
    epochs = 4 if SMOKE else 8
    h = BeaconChainHarness(spec, E, validator_count=16)
    h.extend_chain(epochs * S)
    anchor_slot = None
    srv = HttpApiServer(h.chain).start()
    na = NetworkService(h.chain).start()
    boots, backfill_rates = [], []
    try:
        url = f"http://127.0.0.1:{srv.port}"
        trials = 2 if SMOKE else 3
        for t in range(trials):
            clock = ManualSlotClock(
                genesis_time=HARNESS_GENESIS_TIME,
                seconds_per_slot=spec.seconds_per_slot,
            )
            clock.set_slot(int(h.chain.head_state.slot))
            t0 = time.perf_counter()
            chain = checkpoint_boot(
                url, HotColdDB(MemoryStore()), spec, E, slot_clock=clock
            )
            boots.append(time.perf_counter() - t0)
            anchor_slot = int(chain.anchor_slot)
            nb = NetworkService(chain).start()
            try:
                peer = nb.connect("127.0.0.1", na.port)
                t1 = time.perf_counter()
                stored = nb.sync.backfill(peer)
                dt = time.perf_counter() - t1
                if stored and dt > 0:
                    backfill_rates.append(stored / dt)
            finally:
                nb.stop()
            _partial(trial=t + 1, boot_s=round(boots[-1], 3))
    finally:
        na.stop()
        srv.stop()
    return {
        "metric": "checkpoint_boot_s",
        "value": round(statistics.median(boots), 3),
        "unit": "s to anchored serving chain (fetch+verify+boot)",
        "config": {
            "source_epochs": epochs,
            "anchor_slot": anchor_slot,
            "validators": 16,
            "trials": len(boots),
            "spec": "minimal",
        },
        "sub_metrics": [
            {
                "metric": "checkpoint_backfill_blocks_per_s",
                "value": round(statistics.median(backfill_rates), 1)
                if backfill_rates
                else 0,
                "unit": "blocks/sec backfilled over RPC",
            }
        ],
        "spread": {
            "median_s": round(statistics.median(boots), 3),
            "min_s": round(min(boots), 3),
            "max_s": round(max(boots), 3),
            "trials": len(boots),
        },
    }


def bench_store_soak(jax):
    """Hot-store growth slope with the finality migrator ON vs OFF (the
    `migrator.enabled` A/B seam). With migration every finality advance
    moves finalized blocks cold and prunes hot states, so the hot side's
    byte count flattens after the first finalized epoch; with it off the
    same chain grows the hot side linearly forever. Headline: hot-store
    bytes/epoch over the post-finality tail with migration ON (lower is
    better — the bound the churn-soak oracle enforces); the OFF slope
    and the ON/OFF ratio ride along."""
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    S = E.SLOTS_PER_EPOCH
    epochs = 6 if SMOKE else 10

    def run(migrate):
        h = BeaconChainHarness(spec, E, validator_count=16)
        h.chain.migrator.enabled = migrate
        sizes = []
        for _ep in range(epochs):
            h.extend_chain(S)
            sizes.append(
                h.chain.store.column_stats()["hot"]["total_bytes"]
            )
        return h, sizes

    h_on, on_sizes = run(True)
    _partial(phase="migration_on", hot_bytes=on_sizes[-1])
    h_off, off_sizes = run(False)
    _partial(phase="migration_off", hot_bytes=off_sizes[-1])
    # slope over the post-finality tail only: the first ~3 epochs are
    # pre-finality on both sides and would dilute the contrast
    tail = max(2, epochs // 2)

    def slope(sizes):
        return (sizes[-1] - sizes[-tail]) / (tail - 1)

    slope_on, slope_off = slope(on_sizes), slope(off_sizes)
    # headline is the FINAL hot-store size (positive and stable — a
    # post-finality slope can legitimately go negative, which breaks
    # --compare's relative-regression fraction); slopes ride as details
    return {
        "metric": "store_soak",
        "value": on_sizes[-1],
        "unit": f"hot-store bytes after {epochs} epochs, migration ON",
        "config": {
            "epochs": epochs,
            "tail_epochs": tail,
            "validators": 16,
            "finalized_epoch_on": h_on.finalized_epoch,
            "finalized_epoch_off": h_off.finalized_epoch,
            "split_slot_on": h_on.chain.store.split_slot,
            "spec": "minimal",
        },
        "sub_metrics": [
            {
                "metric": "store_soak_migration_off",
                "value": off_sizes[-1],
                "unit": (
                    f"hot-store bytes after {epochs} epochs, migration "
                    "OFF (control)"
                ),
            }
        ],
        "slopes_bytes_per_epoch": {
            "on_tail": round(slope_on, 1),
            "off_tail": round(slope_off, 1),
        },
        "hot_bytes_per_epoch": {"on": on_sizes, "off": off_sizes},
        "growth_ratio_off_over_on": round(
            off_sizes[-1] / max(on_sizes[-1], 1), 2
        ),
    }


def bench_testnet_churn_soak(jax):
    """Fleet churn soak (the kill/restart regime): every round one node
    of a disk-backed fleet dies with its KV store kept, the fleet runs
    an epoch without it, and it restarts from disk and catches back up —
    the scenario oracle asserts finality never stalls, heads reconverge,
    and the migrator keeps every hot store bounded. Headline: slots
    finalized per wall-second across the whole churn (boot + kill +
    restart + reconvergence included)."""
    from dataclasses import replace

    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.testing.testnet import run_churn_soak_scenario
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    spec = replace(minimal_spec(), altair_fork_epoch=0)
    node_count = 3 if SMOKE else 5
    rounds = 1 if SMOKE else 3
    report = run_churn_soak_scenario(
        spec, E, node_count=node_count, churn_rounds=rounds, seed=2027
    )
    return {
        "metric": "testnet_churn_soak",
        "value": report["finalized_slots_per_wall_s"],
        "unit": (
            f"slots finalized per wall-second ({node_count}-node "
            "disk-backed fleet, kill/restart churn)"
        ),
        "config": {
            "nodes": node_count,
            "churn_rounds": rounds,
            "seed": report["seed"],
            "spec": "minimal",
        },
        "sub_metrics": [
            {
                "metric": "testnet_churn_hot_growth",
                "value": report["hot_store_growth"],
                "unit": "x hot-store growth over churn (migrator bound)",
            }
        ],
        "wall_s": report["wall_s"],
        "finalized_epoch_min": report["finalized_epoch_min"],
        "hot_store_bytes": report["hot_store_bytes"],
        "counters": {
            "kills": REGISTRY.counter(
                "testnet_fault_injections_total"
            ).value(kind="kill"),
            "restarts": REGISTRY.counter(
                "testnet_fault_injections_total"
            ).value(kind="restart"),
            "migrations": REGISTRY.counter(
                "store_migrations_total"
            ).value(),
        },
    }


def bench_fork_choice(jax):
    """Array-program fork choice under a 1M-validator attestation flood:
    per trial, EVERY validator's latest-message vote moves (strictly-newer
    target epoch) across a forked 256-block tree via the batched
    ingestion entry (simulated drained gossip batches of 16384, grouped
    per head root like the network layer's (root, epoch) groups), then
    one `get_head` applies the full 1M-vote delta round. vs_baseline is
    the retained scalar oracle (proto_array_reference) running the SAME
    churn on a 1/16 validator subsample, same run, scaled linearly — the
    oracle's per-validator dict walk is O(votes), so the scaling is
    exact. A riding differential check proves bit-identical head roots
    and node weights at subsample size (batch vs single ingestion,
    proposer boost on/off, equivocations)."""
    import gc

    from lighthouse_tpu.fork_choice.proto_array import ProtoArrayForkChoice
    from lighthouse_tpu.fork_choice.proto_array_reference import (
        ProtoArrayForkChoiceReference,
    )
    from lighthouse_tpu.metrics import REGISTRY

    n_val = 50_000 if SMOKE else 1_000_000
    n_blocks = 64 if SMOKE else 256
    n_heads = min(32, n_blocks // 2)
    batch_size = 16_384

    # 0xAA prefix: an all-zero root is the proto-array's "no vote"
    # sentinel; the anchor must be a realistic non-zero hash
    def root_of(i):
        return b"\xaa" + i.to_bytes(4, "big") + b"\x00" * 27

    tree_rng = random.Random(7)
    edges = [
        (
            i,
            i - 1
            if tree_rng.random() < 0.9
            else tree_rng.randrange(max(1, i - 8), i),
        )
        for i in range(1, n_blocks)
    ]

    def build(cls):
        fc = cls(root_of(0), 0, root_of(0), 0, 0)
        for i, p in edges:
            fc.on_block(
                slot=i, root=root_of(i), parent_root=root_of(p),
                state_root=root_of(i), justified_epoch=0, finalized_epoch=0,
            )
        return fc

    heads = [root_of(i) for i in range(n_blocks - n_heads, n_blocks)]
    rng = np.random.default_rng(11)
    targets = rng.integers(0, n_heads, n_val).astype(np.int64)
    balances = np.full(n_val, 32_000_000_000, dtype=np.uint64)

    fc = build(ProtoArrayForkChoice)
    epoch_counter = [0]

    def run():
        epoch_counter[0] += 1
        epoch = epoch_counter[0]
        for start in range(0, n_val, batch_size):
            chunk_targets = targets[start : start + batch_size]
            base = np.arange(
                start, min(start + batch_size, n_val), dtype=np.int64
            )
            for g in range(n_heads):
                sel = base[chunk_targets == g]
                if sel.size:
                    fc.process_attestation_batch(sel, heads[g], epoch)
        fc.get_head(
            justified_checkpoint_root=root_of(0), justified_epoch=0,
            finalized_epoch=0, justified_state_balances=balances,
        )

    counter = REGISTRY.counter("fork_choice_votes_applied_total")
    before_batch = counter.value(path="batch")
    spans_before = _span_totals(
        ("fork_choice_get_head", "delta_compute", "weight_roll", "best_child")
    )
    run()  # warm-up: first pass allocates the 1M-row columns
    t = _trials(run, n=3, between=gc.collect)
    stages = _span_deltas(
        spans_before,
        _span_totals(
            (
                "fork_choice_get_head",
                "delta_compute",
                "weight_roll",
                "best_child",
            )
        ),
    )
    votes_applied = counter.value(path="batch") - before_batch

    # scalar oracle on a 1/16 subsample, same churn, same run
    sub = n_val // 16
    ref = build(ProtoArrayForkChoiceReference)
    bal_list = [32_000_000_000] * sub
    ctrl_times = []
    for trial in range(2):
        epoch = trial + 1
        t0 = time.perf_counter()
        for v in range(sub):
            ref.process_attestation(v, heads[int(targets[v])], epoch)
        ref.get_head(
            justified_checkpoint_root=root_of(0), justified_epoch=0,
            finalized_epoch=0, justified_state_balances=bal_list,
        )
        ctrl_times.append(time.perf_counter() - t0)
        _partial(control_trial=trial + 1, of=2, s=round(ctrl_times[-1], 4))
    ctrl_scaled = statistics.median(ctrl_times) * 16

    # riding differential: columnar vs oracle, identical subsample votes
    dc = build(ProtoArrayForkChoice)
    dr = build(ProtoArrayForkChoiceReference)
    diff_bal = np.full(sub, 32_000_000_000, dtype=np.uint64)
    for round_i, (boost, eq) in enumerate(
        ((b"\x00" * 32, set()), (heads[0], set()), (b"\x00" * 32, {1, 5}))
    ):
        epoch = round_i + 1
        idx = np.arange(sub, dtype=np.int64)
        for g in range(n_heads):
            sel = idx[targets[:sub] == g]
            if sel.size:
                dc.process_attestation_batch(sel, heads[g], epoch)
        for v in range(sub):
            dr.process_attestation(v, heads[int(targets[v])], epoch)
        kw = dict(
            justified_checkpoint_root=root_of(0), justified_epoch=0,
            finalized_epoch=0,
            proposer_boost_root=boost,
            proposer_boost_amount=1_000_000_000_000 if boost != b"\x00" * 32 else 0,
            equivocating_indices=eq,
        )
        h1 = dc.get_head(justified_state_balances=diff_bal, **kw)
        h2 = dr.get_head(justified_state_balances=list(diff_bal.tolist()), **kw)
        assert h1 == h2, "columnar vs scalar head mismatch"
        w1 = dc.proto_array._weights[: dc.proto_array._n].tolist()
        w2 = [n.weight for n in dr.proto_array.nodes]
        assert w1 == w2, "columnar vs scalar weight mismatch"

    return {
        "metric": "fork_choice_get_head_ms",
        "value": round(t["median_s"] * 1000, 2),
        "unit": (
            f"ms/round ({n_val} votes moved + get_head, "
            f"{n_blocks}-block forked tree)"
        ),
        "vs_baseline": round(ctrl_scaled / t["median_s"], 2),
        "baseline_control": (
            "retained scalar oracle (proto_array_reference) on a 1/16 "
            "validator subsample, same churn, same run, scaled x16"
        ),
        "config": {
            "validators": n_val,
            "blocks": n_blocks,
            "vote_groups": n_heads,
            "batch_size": batch_size,
            "votes_applied_batch": int(votes_applied),
            "oracle_scaled_ms": round(ctrl_scaled * 1000, 1),
            "differential_check": "passed",
        },
        "stages": stages,
        "spread": t,
        "control_spread": {
            "median_s": statistics.median(ctrl_times),
            "min_s": min(ctrl_times),
            "max_s": max(ctrl_times),
            "trials": len(ctrl_times),
        },
    }


def bench_op_pool(jax):
    """Columnar op-pool block packing under a 500k-attestation pool:
    `get_attestations_for_block` as a flat array program (pre-grouped
    buckets, resident masks, one gains vector + np.argmax per round) vs
    the retained rescan reference — which re-hashes every candidate's
    data root and recomputes the full gains list per round — on a 1/16
    bucket subsample, same run, scaled linearly (both walks are
    O(candidates x rounds)). A riding differential check proves the flat
    pack and the rescan pack choose the IDENTICAL attestation list on
    the same subsample pool."""
    import gc

    from lighthouse_tpu.beacon_chain.op_pool import OperationPool
    from lighthouse_tpu.types.containers import build_types
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    n_pool = 20_000 if SMOKE else 500_000
    per_bucket = OperationPool.MAX_AGGREGATES_PER_DATA  # 16
    n_buckets = n_pool // per_bucket
    width = 128  # mainnet-shaped committee

    state, spec, _ = _build_epoch_state(64, resident=True)
    state.slot = int(state.slot) + 1
    t_types = build_types(E)
    current_epoch = int(state.slot) // E.SLOTS_PER_EPOCH
    source = state.current_justified_checkpoint
    target = t_types.Checkpoint(epoch=current_epoch, root=b"\x22" * 32)

    pool = OperationPool(spec, E)
    rng = np.random.default_rng(3)
    build_t0 = time.perf_counter()
    slots = [int(state.slot) - 1 - (i % 4) for i in range(n_buckets)]
    for b in range(n_buckets):
        data = t_types.AttestationData(
            slot=slots[b],
            index=b,
            beacon_block_root=b"\x11" * 32,
            source=source,
            target=target,
        )
        patterns = rng.random((per_bucket, width)) < 0.25
        patterns[:, 0] = True  # overlap on bit 0: no merges, no BLS
        for j in range(per_bucket):
            pool._add_unmerged(
                t_types.Attestation(
                    aggregation_bits=patterns[j].tolist(),
                    data=data,
                    signature=b"\x00" * 96,
                )
            )
        if b and b % 8192 == 0:
            _partial(pool_build_buckets=b, of=n_buckets)
    build_s = time.perf_counter() - build_t0
    assert pool.num_attestations() == n_pool

    def run():
        packed = pool.get_attestations_for_block(state)
        assert 0 < len(packed) <= E.MAX_ATTESTATIONS

    run()  # warm-up (numpy allocators)
    t = _trials(run, n=3, between=gc.collect)

    # rescan reference on a 1/16 bucket subsample, same run
    sub = OperationPool(spec, E)
    sub._attestations = {
        k: v
        for i, (k, v) in enumerate(pool._attestations.items())
        if i % 16 == 0
    }
    ctrl_times = []
    for trial in range(2):
        t0 = time.perf_counter()
        ref_packed = sub.get_attestations_for_block_reference(state)
        ctrl_times.append(time.perf_counter() - t0)
        _partial(control_trial=trial + 1, of=2, s=round(ctrl_times[-1], 4))
    ctrl_scaled = statistics.median(ctrl_times) * 16

    # riding differential: flat vs rescan on the SAME subsample pool
    assert sub.get_attestations_for_block(state) == ref_packed, (
        "flat vs rescan pack mismatch"
    )

    return {
        "metric": "op_pool_pack_ms",
        "value": round(t["median_s"] * 1000, 2),
        "unit": f"ms/pack ({n_pool}-attestation pool, {n_buckets} buckets)",
        "vs_baseline": round(ctrl_scaled / t["median_s"], 2),
        "baseline_control": (
            "retained rescan walk (get_attestations_for_block_reference) "
            "on a 1/16 bucket subsample, same run, scaled x16"
        ),
        "config": {
            "pool_attestations": n_pool,
            "buckets": n_buckets,
            "aggregates_per_bucket": per_bucket,
            "bits_width": width,
            "max_attestations": E.MAX_ATTESTATIONS,
            "pool_build_s": round(build_s, 2),
            "rescan_scaled_ms": round(ctrl_scaled * 1000, 1),
            "differential_check": "passed",
        },
        "spread": t,
        "control_spread": {
            "median_s": statistics.median(ctrl_times),
            "min_s": min(ctrl_times),
            "max_s": max(ctrl_times),
            "trials": len(ctrl_times),
        },
    }


def bench_slasher_ingest(jax):
    """Columnar slasher ingesting ONE EPOCH's full mainnet-shape
    attestation flood at 1M validators (2048 aggregates, ~490-member
    committees, every validator attesting once): per trial, a pre-warmed
    engine (one prior epoch recorded, so min/max spans and record columns
    are populated) consumes the flood queue in one `process_queued` cycle
    — batched data-root hashing, grouped span gather/compare, bulk
    span writebacks. A seeded-slashing RECALL check rides every trial: a
    planted double vote plus surrounds in BOTH directions must all be
    found, exactly, with zero false emissions. vs_baseline is the
    retained scalar engine (slasher/reference.py) on a 1/16 validator
    subsample — same committee size, 1/16 of the committees, same warm
    epoch — same run, scaled linearly (the scalar walk is O(attesting
    indices)). A riding differential check proves columnar ≡ scalar
    emissions on the subsample flood incl. the planted offenders."""
    import gc

    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.slasher.columnar import ColumnarSlasher
    from lighthouse_tpu.slasher.reference import ReferenceSlasher
    from lighthouse_tpu.types.containers import build_types
    from lighthouse_tpu.types.eth_spec import MainnetEthSpec as E

    T = build_types(E)
    n_val = 65_536 if SMOKE else 1_000_000
    n_comm = 128 if SMOKE else 2048  # 64 committees x 32 slots
    warm_epoch, flood_epoch = 10, 11
    # planted offenders: victims of a double vote and both surround
    # directions, detected DURING the timed flood cycle
    v_double, v_surrounded, v_surrounder = 100, 200, 300

    def make_flood(source, target, n_validators, committees, seed):
        rng = np.random.default_rng(seed)
        chunks = np.array_split(rng.permutation(n_validators), committees)
        cp = T.Checkpoint(epoch=source, root=b"\x01" * 32)
        ct = T.Checkpoint(epoch=target, root=b"\x02" * 32)
        return [
            T.IndexedAttestation(
                attesting_indices=np.sort(ch).tolist(),
                data=T.AttestationData(
                    slot=target * E.SLOTS_PER_EPOCH + (i % E.SLOTS_PER_EPOCH),
                    index=i // E.SLOTS_PER_EPOCH,
                    beacon_block_root=b"\x03" * 32,
                    source=cp,
                    target=ct,
                ),
                signature=b"\x00" * 96,
            )
            for i, ch in enumerate(chunks)
        ]

    def single(vi, source, target, root):
        return T.IndexedAttestation(
            attesting_indices=[vi],
            data=T.AttestationData(
                slot=target * E.SLOTS_PER_EPOCH,
                index=0,
                beacon_block_root=root,
                source=T.Checkpoint(epoch=source, root=b"\x01" * 32),
                target=T.Checkpoint(epoch=target, root=b"\x01" * 32),
            ),
            signature=b"\x00" * 96,
        )

    def planted_warm():
        # v_surrounded's wide old record will surround its own honest
        # flood vote; v_surrounder's narrow record gets surrounded by a
        # planted attacker vote in the flood
        return [
            single(v_surrounded, 8, 13, b"\xaa" * 32),
            single(v_surrounder, 11, 12, b"\xbb" * 32),
        ]

    def planted_flood():
        return [
            single(v_double, warm_epoch, flood_epoch, b"\xcc" * 32),
            single(v_surrounder, 10, 13, b"\xdd" * 32),
        ]

    build_t0 = time.perf_counter()
    warm = make_flood(warm_epoch - 1, warm_epoch, n_val, n_comm, seed=1)
    flood = make_flood(warm_epoch, flood_epoch, n_val, n_comm, seed=2)
    build_s = time.perf_counter() - build_t0
    n_atts = len(flood) + len(planted_flood())

    trials = 3
    _partial(stage="warming", engines=trials)
    engines = []
    for _ in range(trials):
        s = ColumnarSlasher(E)
        for a in warm + planted_warm():
            s.accept_attestation(a)
        s.process_queued(warm_epoch)  # untimed: prior-epoch span state
        s.drain_slashings()  # discard warm-cycle findings (the planted
        # wide record itself surrounds its victim's honest warm vote);
        # the timed cycle must find exactly the three planted offenders
        engines.append(s)

    scans = REGISTRY.counter("slasher_exact_scans_total")
    spans_before = _span_totals(
        ("slasher_process", "span_gather", "span_compare", "span_update", "persist")
    )
    scans_before = scans.value()
    recall = {}

    def run():
        s = engines.pop()
        for a in flood + planted_flood():
            s.accept_attestation(a)
        out = s.process_queued(flood_epoch)
        # riding recall assertion: all three planted offenders, nothing else
        assert out["attester_slashings"] == 3, out
        atts, _ = s.drain_slashings()
        offenders = {
            int(
                (
                    set(a.attestation_1.attesting_indices)
                    & set(a.attestation_2.attesting_indices)
                ).pop()
            )
            for a in atts
        }
        assert offenders == {v_double, v_surrounded, v_surrounder}, offenders
        recall["planted"] = 3
        recall["found"] = len(atts)

    t = _trials(run, n=trials, between=gc.collect)
    stages = _span_deltas(
        spans_before,
        _span_totals(
            (
                "slasher_process",
                "span_gather",
                "span_compare",
                "span_update",
                "persist",
            )
        ),
    )
    exact_scans = scans.value() - scans_before

    # scalar reference on a 1/16 subsample: same committee size, 1/16 of
    # the committees (both per-item and per-index costs scale linearly)
    sub_val, sub_comm = n_val // 16, n_comm // 16
    sub_warm = make_flood(warm_epoch - 1, warm_epoch, sub_val, sub_comm, seed=1)
    sub_flood = make_flood(warm_epoch, flood_epoch, sub_val, sub_comm, seed=2)
    ctrl_times = []
    for trial in range(2):
        r = ReferenceSlasher(E)
        for a in sub_warm + planted_warm():
            r.accept_attestation(a)
        r.process_queued(warm_epoch)
        r.drain_slashings()
        for a in sub_flood + planted_flood():
            r.accept_attestation(a)
        t0 = time.perf_counter()
        out = r.process_queued(flood_epoch)
        ctrl_times.append(time.perf_counter() - t0)
        assert out["attester_slashings"] == 3, out
        _partial(control_trial=trial + 1, of=2, s=round(ctrl_times[-1], 4))
    ctrl_scaled = statistics.median(ctrl_times) * 16

    # riding differential: columnar vs scalar on the SAME subsample flood
    dc = ColumnarSlasher(E)
    dr = ReferenceSlasher(E)
    for engine in (dc, dr):
        for a in sub_warm + planted_warm():
            engine.accept_attestation(a)
        engine.process_queued(warm_epoch)
        for a in sub_flood + planted_flood():
            engine.accept_attestation(a)
        engine.process_queued(flood_epoch)
        # fingerprint covers BOTH cycles' emissions (warm incl. the
        # planted wide record's own surround finding)
    fp_c = [
        (a.attestation_1.serialize(), a.attestation_2.serialize())
        for a in dc.drain_slashings()[0]
    ]
    fp_r = [
        (a.attestation_1.serialize(), a.attestation_2.serialize())
        for a in dr.drain_slashings()[0]
    ]
    assert fp_c == fp_r, "columnar vs scalar emission mismatch"

    atts_per_sec = n_atts / t["median_s"]
    ctrl_atts_per_sec = n_atts / ctrl_scaled
    return {
        "metric": "slasher_ingest",
        "value": round(atts_per_sec, 1),
        "unit": (
            f"atts/sec (one epoch's {n_atts}-aggregate mainnet flood at "
            f"{n_val} validators, seeded-slashing recall riding)"
        ),
        "vs_baseline": round(atts_per_sec / ctrl_atts_per_sec, 2),
        "baseline_control": (
            "retained scalar engine (slasher/reference.py) on a 1/16 "
            "validator subsample (1/16 of the committees, same committee "
            "size), same run, scaled x16"
        ),
        "config": {
            "validators": n_val,
            "aggregates": n_atts,
            "committee_size": n_val // n_comm,
            "cycle_ms": round(t["median_s"] * 1000, 1),
            "validator_attestations_per_sec": round(n_val / t["median_s"]),
            "scalar_scaled_ms": round(ctrl_scaled * 1000, 1),
            "flood_build_s": round(build_s, 2),
            "exact_scans": int(exact_scans),
            "recall": recall,
            "differential_check": "passed",
        },
        "stages": stages,
        "spread": t,
        "control_spread": {
            "median_s": statistics.median(ctrl_times),
            "min_s": min(ctrl_times),
            "max_s": max(ctrl_times),
            "trials": len(ctrl_times),
        },
    }


def bench_api_throughput(jax):
    """The Beacon API serving tier at the 1M-validator state: the
    full-table `/states/head/validators` response assembled zero-copy
    from the resident columns (PR 14) under three regimes — COLD (cache
    cleared per request: the assembly cost), HOT (head-keyed response
    cache: the steady dashboard-fleet case), and a PAGINATED SCAN
    (1000-row pages sweeping the whole table cold: slice-gather cost).
    vs_baseline is the retained per-object oracle
    (`state_validators_reference`) rendering the SAME full table in the
    same run, and the cold body must be BYTE-IDENTICAL to the oracle's
    compact JSON — the riding differential."""
    import gc

    from lighthouse_tpu.beacon_chain.events import ServerSentEventHandler
    from lighthouse_tpu.http_api import BeaconApi
    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    n = 20_000 if SMOKE else 1_000_000
    page = 500 if SMOKE else 1000
    # the full-table body (~460 MB at 1M) must fit the cache for the hot
    # regime to exercise it
    os.environ["LIGHTHOUSE_TPU_API_CACHE_BYTES"] = str(2 << 30)
    state, _vs = _build_1m_state(n)
    # diversify ~n/256 rows so the status vectorization and filters see
    # every spec family, not one constant
    rng = random.Random(3)
    far = 2**64 - 1
    for _ in range(max(64, n // 256)):
        v = state.validators.mutate(rng.randrange(n))
        kind = rng.randrange(4)
        if kind == 0:
            v.exit_epoch, v.withdrawable_epoch = 0, 9  # exited
        elif kind == 1:
            v.slashed, v.exit_epoch, v.withdrawable_epoch = True, 3, 9
        elif kind == 2:
            v.activation_epoch, v.activation_eligibility_epoch = far, far
        else:
            v.exit_epoch, v.withdrawable_epoch = 0, 0  # withdrawal
    _partial(fixture="diversified")

    class _Chain:
        pass

    chain = _Chain()
    chain.head_state = state
    chain.head_root = b"\xab" * 32
    chain._states = {chain.head_root: state}
    chain._blocks_by_root = {}
    chain.genesis_block_root = chain.head_root
    chain.genesis_validators_root = bytes(state.genesis_validators_root)
    chain.event_handler = ServerSentEventHandler()
    chain.E = E
    chain.store = None
    api = BeaconApi(chain)

    spans_before = _span_totals(("cache_lookup", "assemble", "serialize"))
    assembled = REGISTRY.counter("api_columnar_assembly_total")
    assembled_before = assembled.value(route="validators")
    hits = REGISTRY.counter("api_cache_hits_total")
    hits_before = hits.value(route="validators")

    # -- cold: full-table assembly, RESPONSE cache cleared per request ---
    # (one untimed warm-up first: the resident assembly caches — index
    # pieces, per-column hexlify pieces — build once per column
    # residency, exactly like a serving node's steady state; "cold"
    # means the response cache missed, not that the process is fresh)
    body_box = {}

    def cold():
        api.response_cache.clear()
        body_box["body"], _ = api.serve_state_validators("head")

    t0 = time.perf_counter()
    cold()
    _partial(warmup_s=round(time.perf_counter() - t0, 3))
    gc.collect()
    t_cold = _trials(cold, n=5, label="cold_trial", between=gc.collect)
    body = body_box["body"]

    # -- per-object oracle on the SAME full table, same run --------------
    ref_box = {}

    def oracle():
        ref_box["ref"] = json.dumps(
            api.state_validators_reference(state), separators=(",", ":")
        ).encode()

    t_oracle = _trials(oracle, n=2, label="oracle_trial", between=gc.collect)
    assert body == ref_box["ref"], (
        "columnar full-table body differs from the per-object oracle"
    )
    del ref_box
    gc.collect()

    # -- hot: the head-keyed response cache serves the cached body -------
    api.serve_state_validators("head")  # prime

    hot_batch = 50 if SMOKE else 200

    def hot():
        for _ in range(hot_batch):
            api.serve_state_validators("head")

    t_hot = _trials(hot, n=3, label="hot_trial")
    hot_rps = hot_batch / t_hot["median_s"]
    lat = []
    for _ in range(500):
        t0 = time.perf_counter()
        api.serve_state_validators("head")
        lat.append(time.perf_counter() - t0)
    lat.sort()
    hot_p50_us = lat[len(lat) // 2] * 1e6
    hot_p99_us = lat[int(len(lat) * 0.99)] * 1e6

    # -- paginated scan: 1000-row pages sweep the whole table cold -------
    api.response_cache.clear()
    page_lat = []
    t0 = time.perf_counter()
    for off in range(0, n, page):
        p0 = time.perf_counter()
        api.serve_state_validators(
            "head", {"limit": str(page), "offset": str(off)}
        )
        page_lat.append(time.perf_counter() - p0)
    paginated_s = time.perf_counter() - t0
    page_lat.sort()
    pages = len(page_lat)
    _partial(paginated_pages=pages, s=round(paginated_s, 3))

    # the zero-copy floor: the SSZ balances body is one interleave
    api.response_cache.clear()
    t0 = time.perf_counter()
    ssz_body, _ = api.serve_state_validator_balances("head", ssz=True)
    ssz_ms = (time.perf_counter() - t0) * 1000
    assert len(ssz_body) == n * 16

    # -- multi-process serving workers (PR 18): the same columns behind
    # the pre-fork accept tier, measured through real HTTP ---------------
    import hashlib
    import threading
    import urllib.request

    from lighthouse_tpu.http_api import HttpApiServer

    cores = os.cpu_count() or 1
    client_threads = 4 if SMOKE else 8
    load_s = 2.0 if SMOKE else 4.0
    page_offsets = (0, (n // 2 // page) * page, ((n - page) // page) * page)
    table_path = "/eth/v1/beacon/states/head/validators"

    def _digest_get(port, path):
        """(headers, sha256, size) — streamed, so full-table bodies never
        pile up in client memory. `http.client` returns a silent short
        read when the peer closes mid-body under sized reads (no
        IncompleteRead), so the Content-Length is re-checked: a transfer
        truncated by a retiring worker's proxy leg must surface as a
        retryable fault, not digest as a complete body."""
        req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
        with urllib.request.urlopen(req, timeout=120) as r:
            hasher = hashlib.sha256()
            size = 0
            while True:
                chunk = r.read(1 << 20)
                if not chunk:
                    break
                hasher.update(chunk)
                size += len(chunk)
            cl = r.headers.get("Content-Length")
            if cl is not None and size != int(cl):
                raise IOError(
                    f"truncated transfer: {size} of {cl} bytes from {path}"
                )
            return dict(r.headers), hasher.hexdigest(), size

    def _load(port, seconds):
        """Concurrent paginated-page GETs (the small-body dashboard
        workload — full-table transfers would measure loopback bandwidth,
        not the serving tier) for `seconds`; returns (req/sec, errors)."""
        stop_at = time.perf_counter() + seconds
        counts = [0] * client_threads
        errors = [0] * client_threads

        def run(i):
            k = i
            while time.perf_counter() < stop_at:
                off = page_offsets[k % len(page_offsets)]
                k += 1
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{table_path}"
                        f"?limit={page}&offset={off}",
                        timeout=30,
                    ) as r:
                        r.read()
                    counts[i] += 1
                except Exception:  # noqa: BLE001 — tallied, asserted zero
                    errors[i] += 1

        threads = [
            threading.Thread(target=run, args=(i,))
            for i in range(client_threads)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return sum(counts) / wall, sum(errors)

    def _burst_digests(port, names, attempts=10):
        """Bursts of concurrent full-table GETs until every server id in
        `names` has answered; {server_id: (digest, size)}. Concurrency is
        what spreads the accepts — sequential requests can all land on
        one replica. Per-request faults (a retiring worker's socket
        handover mid-rotation) are retried across attempts; only a final
        round that still faults, or never covering `names`, fails."""
        seen = {}
        faults = []
        for _ in range(attempts):
            results, faults = [], []

            def one():
                try:
                    hd, dg, size = _digest_get(port, table_path)
                    results.append((hd["X-Api-Served-By"], dg, size))
                except Exception as e:  # noqa: BLE001 — asserted below
                    faults.append(e)

            burst = [
                threading.Thread(target=one)
                for _ in range(min(client_threads, 4))
            ]
            for t in burst:
                t.start()
            for t in burst:
                t.join()
            for who, dg, size in results:
                seen[who] = (dg, size)
            if not faults and names <= set(seen):
                return seen
        assert not faults, f"full-table burst kept failing: {faults[0]!r}"
        raise AssertionError(
            f"server ids seen {sorted(seen)} never covered {sorted(names)}"
        )

    def _assert_identical(seen, parent_digest, parent_size, when):
        bad = {
            who: (dg[:16], size)
            for who, (dg, size) in sorted(seen.items())
            if dg != parent_digest
        }
        assert not bad, (
            f"{when} replica body diverged from the parent "
            f"(parent {parent_digest[:16]}/{parent_size}B): {bad}"
        )

    respawns = REGISTRY.counter("api_worker_respawns_total")
    w_axis = {}
    for W in (1, 4):
        srv = HttpApiServer(chain, workers=W)
        # prime the response cache BEFORE start(): the fork inherits the
        # hot full-table body by CoW — every replica is born warm
        srv.api.serve_state_validators("head")
        srv.start()
        try:
            ready_by = time.monotonic() + 20
            while True:
                try:
                    _digest_get(srv.port, "/eth/v1/node/health")
                    break
                except Exception:  # noqa: BLE001 — replicas still booting
                    if time.monotonic() > ready_by:
                        raise
                    time.sleep(0.1)
            rps, errs = _load(srv.port, load_s)
            assert errs == 0, f"workers={W}: {errs} failed requests"
            w_axis[W] = round(rps, 1)
            _partial(workers=W, paginated_rps=w_axis[W])
            if W == 4:
                # full-table bodies byte-identical from EVERY replica
                # (compared by streamed digest against the parent's serve)
                _, parent_digest, parent_size = _digest_get(
                    srv.parent_port, table_path
                )
                assert parent_size == len(body)
                names = {w["name"] for w in srv._pool.worker_info()}
                assert len(names) == 4
                seen = _burst_digests(srv.port, names)
                _assert_identical(seen, parent_digest, parent_size, "steady")
                # …and across a head-change invalidation: stale replicas
                # forward to the parent, the supervisor rotates them onto
                # a fresh CoW snapshot, and the bytes never waver.
                # Rotation is DEMAND-driven (ApiWorkerPool rotates only
                # after a stale forward reaches the parent), so keep reads
                # flowing while waiting — a single probe can race the
                # replicas' generation-event pipes and leave the pool
                # without any demand signal, stalling rotation forever
                r_before = respawns.value(reason="head_refresh")
                chain.event_handler.register_head(
                    chain.head_root, int(state.slot), b"\x11" * 32
                )
                rotate_by = time.monotonic() + 30
                while respawns.value(reason="head_refresh") == r_before:
                    _, dg, size = _digest_get(srv.port, table_path)
                    assert dg == parent_digest, (
                        f"mid-rotation body diverged: {dg[:16]}/{size}B vs "
                        f"parent {parent_digest[:16]}/{parent_size}B"
                    )
                    assert time.monotonic() < rotate_by, (
                        "head event never rotated the replicas"
                    )
                    time.sleep(0.1)
                seen = _burst_digests(
                    srv.port, {w["name"] for w in srv._pool.worker_info()}
                )
                _assert_identical(
                    seen, parent_digest, parent_size, "post-rotation"
                )
                _partial(workers=4, identity="passed", rotations=int(
                    respawns.value(reason="head_refresh") - r_before
                ))
        finally:
            srv.stop()
    speedup = round(w_axis[4] / w_axis[1], 2) if w_axis[1] else 0.0
    if cores >= 4:
        assert speedup >= 1.8, (
            f"workers=4 speedup {speedup}x < 1.8x on {cores} cores"
        )
    else:
        # a 1-core box cannot show parallel speedup; the floor asserts
        # the tier doesn't grossly TAX throughput. Four processes
        # time-slicing one core pay real scheduler overhead (~0.75-0.85x
        # observed), hence 0.7, not 1.0
        floor = float(os.environ.get("BENCH_API_WORKERS_MIN_RATIO", "0.7"))
        assert speedup >= floor, (
            f"workers=4 at {speedup}x of workers=1 on {cores} core(s) — "
            f"below the no-regression floor {floor}"
        )

    stages = _span_deltas(
        spans_before, _span_totals(("cache_lookup", "assemble", "serialize"))
    )
    return {
        "metric": "api_throughput",
        "value": round(hot_rps, 1),
        "unit": (
            f"req/sec (hot-cache full-table validators at {n} validators)"
        ),
        "vs_baseline": round(t_oracle["median_s"] / t_cold["median_s"], 2),
        "baseline_control": (
            "retained per-object oracle (state_validators_reference) on "
            "the SAME full table, same run; cold columnar body asserted "
            "byte-identical to it"
        ),
        "config": {
            "validators": n,
            "body_bytes": len(body),
            "cold_full_table_ms": round(t_cold["median_s"] * 1000, 1),
            "oracle_full_table_ms": round(t_oracle["median_s"] * 1000, 1),
            "hot_cache": {
                "rps": round(hot_rps, 1),
                "p50_us": round(hot_p50_us, 1),
                "p99_us": round(hot_p99_us, 1),
            },
            "paginated_scan": {
                "pages": pages,
                "page_rows": page,
                "rps": round(pages / paginated_s, 1),
                "p50_ms": round(page_lat[pages // 2] * 1000, 2),
                "p99_ms": round(page_lat[int(pages * 0.99)] * 1000, 2),
            },
            "balances_ssz_full_table_ms": round(ssz_ms, 2),
            "columnar_requests": int(
                assembled.value(route="validators") - assembled_before
            ),
            "cache_hits": int(hits.value(route="validators") - hits_before),
            "differential_check": "passed",
            "workers_axis": {
                "cores": cores,
                "client_threads": client_threads,
                "workers1_rps": w_axis[1],
                "workers4_rps": w_axis[4],
                "speedup": speedup,
                "full_table_identity": "passed",
                "head_refresh_identity": "passed",
            },
        },
        "sub_metrics": [
            {
                "metric": "api_throughput_workers1",
                "value": w_axis[1],
                "unit": (
                    f"req/sec (paginated pages via HTTP, workers=1, "
                    f"{cores} cores)"
                ),
            },
            {
                "metric": "api_throughput_workers4",
                "value": w_axis[4],
                "unit": (
                    f"req/sec (paginated pages via HTTP, workers=4, "
                    f"{cores} cores)"
                ),
            },
        ],
        "stages": stages,
        "spread": t_cold,
        "control_spread": t_oracle,
    }


def bench_sse_fanout(jax):
    """The SSE broadcast fan-out tier (PR 18) at dashboard-fleet scale:
    one handler, 10k subscribers, head events published at a paced
    cadence (a burst would just measure queue backlog). Each event is
    serialized ONCE and the shared frame lands on every matching
    subscriber queue via the dedicated broadcast thread; sentinel drainer
    threads measure publish→drain lag end to end. A separate phase proves
    slow-consumer eviction is drop-counted, never blocking the publisher.
    vs_baseline is the naive tier — re-serializing per subscriber —
    measured over the same subscriber population, same run."""
    import gc
    import threading

    from lighthouse_tpu.beacon_chain import events as ev_mod
    from lighthouse_tpu.beacon_chain.events import (
        EventSubscription,
        ServerSentEventHandler,
        sse_frame,
    )
    from lighthouse_tpu.metrics import REGISTRY

    dropped = REGISTRY.counter("sse_dropped_total")
    delivered = REGISTRY.counter("sse_events_delivered_total")
    serialized = REGISTRY.counter("sse_events_serialized_total")
    drop_reasons = ("slow_consumer", "evicted", "publish_overflow")

    subs_small = 200 if SMOKE else 1000
    subs_big = 1000 if SMOKE else 10_000
    events_small = 50 if SMOKE else 200
    events_big = 20 if SMOKE else 40
    sentinels = 8
    pace_small_s = 0.002
    pace_big_s = 0.05
    p99_cap_ms = float(os.environ.get("BENCH_SSE_P99_MS", "250"))

    def publish(h, count, pace_s, start=0):
        for i in range(count):
            h.register_head(bytes([i % 256]) * 32, start + i, b"\x01" * 32)
            if pace_s:
                time.sleep(pace_s)

    h = ServerSentEventHandler()

    # -- phase 1: 1k subscribers, ZERO drops at paced head cadence -------
    subs = [h.subscribe(["head"]) for _ in range(subs_small)]
    drops_before = {r: dropped.value(reason=r) for r in drop_reasons}
    ser_before = serialized.value()
    publish(h, events_small, pace_small_s)
    assert h.flush(60.0)
    for r, v in drops_before.items():
        assert dropped.value(reason=r) == v, f"phase-1 drops (reason={r})"
    # serialize-once: one frame per EVENT, not per (event, subscriber)
    assert serialized.value() - ser_before == events_small
    # queue cap (256) above the event count: nothing displaced anywhere
    for s in (subs[0], subs[len(subs) // 2], subs[-1]):
        assert s._q.qsize() == events_small
    for s in subs:
        h.unsubscribe(s)
    _partial(phase="zero_drops", subscribers=subs_small, events=events_small)
    gc.collect()

    # -- phase 2: 10k subscribers, sentinel-measured publish→drain lag ---
    subs = [h.subscribe(["head"]) for _ in range(subs_big - sentinels)]
    sentinel_subs = [h.subscribe(["head"]) for _ in range(sentinels)]
    lags, lag_lock = [], threading.Lock()
    stop = threading.Event()

    def drain(sub):
        local = []
        while True:
            rec = sub.poll_record(timeout=0.05)
            if rec is not None:
                local.append(time.monotonic() - rec[2])
            elif stop.is_set():
                break
        with lag_lock:
            lags.extend(local)

    drainers = [
        threading.Thread(target=drain, args=(s,)) for s in sentinel_subs
    ]
    for t in drainers:
        t.start()
    del_before = delivered.value()
    drops_before = {r: dropped.value(reason=r) for r in drop_reasons}
    t0 = time.perf_counter()
    publish(h, events_big, pace_big_s, start=1000)
    assert h.flush(120.0)
    fan_wall = time.perf_counter() - t0
    stop.set()
    for t in drainers:
        t.join(30.0)
    deliveries = delivered.value() - del_before
    assert deliveries == events_big * subs_big
    rate = deliveries / fan_wall
    for r, v in drops_before.items():
        assert dropped.value(reason=r) == v, f"phase-2 drops (reason={r})"
    assert len(lags) == events_big * sentinels
    lags.sort()
    lag_p50_ms = lags[len(lags) // 2] * 1000
    lag_p99_ms = lags[int(len(lags) * 0.99)] * 1000
    assert lag_p99_ms < p99_cap_ms, (
        f"p99 publish→drain lag {lag_p99_ms:.1f} ms ≥ {p99_cap_ms} ms"
    )
    _partial(
        phase="fanout",
        subscribers=subs_big,
        deliveries_per_sec=round(rate, 1),
        p99_ms=round(lag_p99_ms, 2),
    )

    # -- control: the naive tier serializes per SUBSCRIBER ---------------
    # (same population size, same _offer machinery, same run; the only
    # difference is where sse_frame runs — the shared-frame economics)
    ctrl = [EventSubscription(("head",)) for _ in range(subs_big)]
    ev = {
        "topic": "head",
        "data": {
            "slot": "1",
            "block": "0x" + "ab" * 32,
            "state": "0x" + "cd" * 32,
        },
    }
    rounds = 3
    t0 = time.perf_counter()
    for _ in range(rounds):
        for s in ctrl:
            s._offer((ev, sse_frame(ev).encode(), t0))
    naive_s = (time.perf_counter() - t0) / rounds
    t0 = time.perf_counter()
    for _ in range(rounds):
        frame = sse_frame(ev).encode()
        for s in ctrl:
            s._offer((ev, frame, t0))
    shared_s = (time.perf_counter() - t0) / rounds
    vs_baseline = round(naive_s / shared_s, 2) if shared_s else 0.0
    del ctrl
    for s in subs + sentinel_subs:
        h.unsubscribe(s)
    gc.collect()

    # -- phase 3: a wedged consumer is evicted, never blocks -------------
    stuck = h.subscribe(["head"])
    evict_before = dropped.value(reason="evicted")
    slow_before = dropped.value(reason="slow_consumer")
    t0 = time.perf_counter()
    publish(h, ev_mod._QUEUE_CAP + ev_mod._EVICT_AFTER, 0.0, start=5000)
    publish_wall = time.perf_counter() - t0
    assert h.flush(60.0)
    assert stuck.evicted and stuck.closed
    assert dropped.value(reason="evicted") == evict_before + 1
    slow_drops = dropped.value(reason="slow_consumer") - slow_before
    assert slow_drops >= ev_mod._EVICT_AFTER
    assert publish_wall < 5.0, (
        f"publisher spent {publish_wall:.2f}s — it must never block on a "
        "wedged consumer"
    )
    h.close()

    return {
        "metric": "sse_fanout",
        "value": round(rate, 1),
        "unit": (
            f"deliveries/sec ({subs_big} subscribers, paced head events)"
        ),
        "vs_baseline": vs_baseline,
        "baseline_control": (
            "per-subscriber re-serialization (naive tier) over the same "
            f"{subs_big}-subscriber population, same run — the shared-"
            "frame economics"
        ),
        "config": {
            "subscribers": subs_big,
            "events": events_big,
            "pace_ms": pace_big_s * 1000,
            "sentinel_drainers": sentinels,
            "lag_p50_ms": round(lag_p50_ms, 2),
            "lag_p99_ms": round(lag_p99_ms, 2),
            "p99_cap_ms": p99_cap_ms,
            "zero_drop_phase": {
                "subscribers": subs_small,
                "events": events_small,
                "drops": 0,
            },
            "eviction_phase": {
                "slow_consumer_drops": int(slow_drops),
                "evictions": 1,
                "publish_wall_s": round(publish_wall, 3),
            },
            "queue_cap": ev_mod._QUEUE_CAP,
            "evict_after": ev_mod._EVICT_AFTER,
        },
    }


_VC_STAGES = (
    "vc_duty_cycle",
    "vc_fetch",
    "vc_assemble",
    "vc_protect",
    "vc_sign_batch",
    "vc_publish",
)


def _build_vc_state(n):
    """A resident n-validator state parked at an epoch start, with
    DISTINCT per-validator pubkeys and matching secret-key scalars.

    `_build_epoch_state` clones validator 0's pubkey across the registry
    (epoch sweeps never look at it) — the VC duty cycle DOES: duties
    resolve index->pubkey and the store signs by pubkey, so every key
    must be unique. Registry identities are synthetic (index-derived 48
    bytes): deriving n real G1 pubkeys is n scalar muls of setup the
    duty cycle never touches, while signing identity is sk-only — the
    per-key oracle and the batch path sign with the same scalars either
    way, so the bit-identity assertion is unaffected."""
    import hashlib as _h
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.chain import _make_persistent
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import R
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.state_processing.registry_columns import (
        registry_columns_for,
    )
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec

    class _VcBenchSpec(MinimalEthSpec):
        """Minimal preset with the committee axis widened 4 -> 8.
        Minimal's 4-committee cap would shear 100k keys into 32
        committees of 3125 — over the SSZ Bitlist limit
        (MAX_VALIDATORS_PER_COMMITTEE = 2048), a shape no preset can
        express. 8/slot gives 64 committees of ~1562: legal, and the
        mainnet-like regime where a whole committee shares one
        AttestationData (the grouping the batch signer amortizes)."""

        MAX_COMMITTEES_PER_SLOT = 8

    E = _VcBenchSpec
    bls.set_backend("host")  # real signing: the metric IS the signing
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    base = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    v0 = base.validators[0]
    vs, bal, sks = [], [], []
    for i in range(n):
        v = v0.copy()
        v.pubkey = (
            _h.sha256(b"vc_pk" + i.to_bytes(4, "little")).digest()
            + i.to_bytes(16, "little")
        )
        v.withdrawal_credentials = i.to_bytes(32, "little")
        vs.append(v)
        bal.append(32_000_000_000)
        sks.append(
            bls.SecretKey(
                1
                + int.from_bytes(
                    _h.sha256(b"vc_sk" + i.to_bytes(4, "little")).digest(),
                    "big",
                )
                % (R - 1)
            )
        )
    base.validators = vs
    base.balances = bal
    base.previous_epoch_participation = bytearray(n)
    base.current_epoch_participation = bytearray(n)
    base.inactivity_scores = [0] * n
    # epoch-3 start: the epoch's 8 duty slots never cross a boundary, so
    # per-slot head advances stay slot-processing, not epoch transitions
    # (epoch_transition_100k already owns that number)
    base.slot = 3 * E.SLOTS_PER_EPOCH
    _make_persistent(base)
    cols = registry_columns_for(base)
    if cols is not None:  # None under LIGHTHOUSE_TPU_RESIDENT_COLUMNS=0
        cols.refresh(base)
    return base, spec, E, sks


def bench_vc_epoch_100k(jax):
    """One epoch's full attestation duty cycle at 100k keys in ONE VC
    process (PR 19 tentpole): per slot, the batch pipeline fetches duties
    (one bulk epoch-duty-table fetch, cached for the epoch), advances the
    head state, assembles ONE AttestationData per committee, runs
    slashing protection as one transaction, signs through the grouped
    fixed-base batch signer, and publishes — 100k real BLS signatures
    over the epoch's 32 distinct messages.

    vs_baseline is the retained per-key oracle (`sign_attestation` per
    duty: domain + hash_tree_root + per-entry sqlite commit + hash_to_g2
    + generic pt_mul) on a 1/64 key subsample, same run, scaled to n —
    composed with the batch run's OWN fetch/assemble/publish cost so the
    shared stages are counted once at measured cost instead of being
    inflated by the extrapolation. In-bench asserts: every subsample
    signature bit-identical between the two paths, slashing-DB rows for
    the subsample identical, zero refusals on both, and (full scale)
    >=5x over the composed oracle estimate."""
    import gc
    from types import SimpleNamespace

    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.validator_client import (
        AttestationService,
        DutiesService,
        LocalBeaconNode,
        LocalKeystoreSigner,
        ValidatorStore,
    )

    n = 2_000 if SMOKE else 100_000
    state, spec, E, sks = _build_vc_state(n)
    pk_of = [bytes(v.pubkey) for v in state.validators]

    class _RecordingNode(LocalBeaconNode):
        """LocalBeaconNode over a chain-shaped shim: real bulk-duties
        surface (the epoch duty table), but publishes are counted, not
        imported — the measurement is the VC pipeline, not block-side
        attestation processing (attestation_batch owns that)."""

        def __init__(self, st):
            super().__init__(SimpleNamespace(head_state=st, E=E))
            self.published = 0

        def publish_attestations(self, attestations):
            self.published += len(attestations)

    node = _RecordingNode(state)
    store = ValidatorStore()
    t0 = time.perf_counter()
    for pk, sk in zip(pk_of, sks):
        store.add_validator(pk, LocalKeystoreSigner(sk))
    _partial(stage="register", keys=n, s=round(time.perf_counter() - t0, 2))
    duties_svc = DutiesService(store, node, spec, E)
    svc = AttestationService(duties_svc, store, node, spec, E)

    head = b"\x42" * 32
    start = int(state.slot)
    refusals = REGISTRY.counter("vc_slashing_protection_refusals_total")
    refusals_before = refusals.value()
    spans_before = _span_totals(_VC_STAGES)

    batch_sigs = {}
    states_by_slot = {}
    slot_walls = []
    t0 = time.perf_counter()
    for slot in range(start, start + E.SLOTS_PER_EPOCH):
        s0 = time.perf_counter()
        out = svc.attest(slot, head)
        slot_walls.append(round(time.perf_counter() - s0, 3))
        _partial(slot=slot - start + 1, of=E.SLOTS_PER_EPOCH,
                 s=slot_walls[-1], sigs=len(out))
        # follow the chain: the advanced state becomes the next head, so
        # each fetch advances one slot (the steady-state VC shape)
        states_by_slot[slot] = svc._last_attested[1]
        node.chain.head_state = svc._last_attested[1]
        epoch_duties = duties_svc.attester_duties(
            (slot // E.SLOTS_PER_EPOCH)
        )  # cached: the ONE bulk fetch happened at the epoch's first slot
        slot_duties = [d for d in epoch_duties if d.slot == slot]
        assert len(out) == len(slot_duties), "refusal in a clean run"
        for duty, att in zip(slot_duties, out):
            batch_sigs[duty.validator_index] = bytes(att.signature)
        del out
    wall = time.perf_counter() - t0
    stages = _span_deltas(spans_before, _span_totals(_VC_STAGES))
    assert node.published == n, f"published {node.published}, expected {n}"
    assert refusals.value() == refusals_before, "refusals in a clean run"
    keyed_batch_s = sum(
        stages[s]["mean_ms"] / 1000 * stages[s]["samples"]
        for s in ("vc_protect", "vc_sign_batch")
        if s in stages
    )
    gc.collect()

    # -- per-key oracle on a 1/64 subsample, same states, same duties ----
    epoch_duties = duties_svc.attester_duties(start // E.SLOTS_PER_EPOCH)
    ctrl_set = set(range(0, n, 64))  # uniform over committees via shuffle
    ctrl_jobs = [d for d in epoch_duties if d.validator_index in ctrl_set]
    assert len(ctrl_jobs) == len(ctrl_set), "every key has exactly one duty"
    ctrl_store = ValidatorStore()
    for vi in sorted(ctrl_set):
        ctrl_store.add_validator(pk_of[vi], LocalKeystoreSigner(sks[vi]))
    ctrl_sigs = {}
    t0 = time.perf_counter()
    for duty in ctrl_jobs:
        st = states_by_slot[duty.slot]
        data = svc._attestation_data(st, duty.slot, head, duty.committee_index)
        ctrl_sigs[duty.validator_index] = ctrl_store.sign_attestation(
            pk_of[duty.validator_index], data, st, spec, E
        )
    ctrl_s = time.perf_counter() - t0
    _partial(stage="control", keys=len(ctrl_jobs), s=round(ctrl_s, 2))

    # composed oracle estimate: shared fetch/assemble/publish at the
    # batch run's own measured cost, keyed stages at the per-key rate
    ctrl_scaled = ctrl_s * (n / len(ctrl_jobs))
    oracle_epoch_s = (wall - keyed_batch_s) + ctrl_scaled
    speedup = oracle_epoch_s / wall

    # -- riding differential asserts -------------------------------------
    for vi in ctrl_set:
        assert batch_sigs[vi] == ctrl_sigs[vi], (
            f"batch signature for validator {vi} diverges from per-key"
        )
    q = (
        "SELECT a.source_epoch, a.target_epoch, a.signing_root "
        "FROM signed_attestations a JOIN validators v "
        "ON a.validator_id = v.id WHERE v.pubkey = ? "
        "ORDER BY a.target_epoch"
    )
    for vi in ctrl_set:
        batch_rows = store.slashing_db._conn.execute(q, (pk_of[vi],)).fetchall()
        ctrl_rows = ctrl_store.slashing_db._conn.execute(
            q, (pk_of[vi],)
        ).fetchall()
        assert batch_rows == ctrl_rows, f"slashing rows diverge for {vi}"
    if not SMOKE:
        assert speedup >= 5.0, (
            f"batch duty cycle {speedup:.2f}x per-key oracle — below the 5x "
            "floor"
        )

    distinct = len({(d.slot, d.committee_index) for d in epoch_duties})
    return {
        "metric": "vc_epoch_100k",
        "value": round(wall, 2),
        "unit": f"s/epoch ({n} keys, full attestation duty cycle)",
        "vs_baseline": round(speedup, 2),
        "baseline_control": (
            "per-key oracle (sign_attestation per duty: domain + "
            "hash_tree_root + per-entry sqlite commit + hash_to_g2 + "
            "generic pt_mul) on a 1/64 subsample x64, same run, composed "
            "with the batch run's own shared-stage cost"
        ),
        "config": {
            "keys": n,
            "signatures": node.published,
            "signatures_per_sec": round(n / wall, 1),
            "distinct_messages": distinct,
            "slot_walls_s": slot_walls,
            "keyed_stages_s": round(keyed_batch_s, 2),
            "control_keys": len(ctrl_jobs),
            "control_s": round(ctrl_s, 2),
            "control_scaled_s": round(ctrl_scaled, 2),
            "oracle_epoch_est_s": round(oracle_epoch_s, 2),
            "refusals": 0,
        },
        "stages": stages,
        "spread": {
            "median_s": wall, "min_s": wall, "max_s": wall, "trials": 1,
        },
    }


_METRICS = {
    "merkle": bench_merkle,
    "pairing": bench_pairing,
    "block_import": bench_block_import,
    "block_production": bench_block_production,
    "epoch_transition": bench_epoch_transition,
    "epoch_transition_1m": bench_epoch_transition_1m,
    "state_root": bench_state_root,
    "epoch_reroot": bench_epoch_reroot,
    "kzg": bench_kzg,
    "da_verify": bench_da_verify,
    "da_withholding": bench_da_withholding,
    "bls": bench_bls,
    "sync_catchup": bench_sync_catchup,
    "gossip_soak": bench_gossip_soak,
    "testnet_soak": bench_testnet_soak,
    "attestation_batch": bench_attestation_batch,
    "fork_choice": bench_fork_choice,
    "op_pool": bench_op_pool,
    "slasher_ingest": bench_slasher_ingest,
    "api_throughput": bench_api_throughput,
    "sse_fanout": bench_sse_fanout,
    "vc_epoch_100k": bench_vc_epoch_100k,
    "checkpoint_boot_s": bench_checkpoint_boot,
    "store_soak": bench_store_soak,
    "testnet_churn_soak": bench_testnet_churn_soak,
}


def _metric_cap(name: str, default: float) -> float:
    """Per-metric wall-clock cap, overridable via BENCH_TIMEOUT_<METRIC>
    (seconds; 0 skips the metric). On 1-core images the device-compile
    metrics (kzg, bls) blow any default cap — BENCH_TIMEOUT_KZG=0
    BENCH_TIMEOUT_BLS=0 turns their recurring `timed out` errors into an
    explicit, documented skip; on TPU hosts a larger override buys the
    cold compile a real chance instead."""
    raw = os.environ.get(f"BENCH_TIMEOUT_{name.upper()}")
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _collect_partials(stdout) -> list:
    """Pull `PARTIAL {...}` progress lines out of a (possibly dead)
    subprocess's stdout."""
    if not stdout:
        return []
    if isinstance(stdout, bytes):
        stdout = stdout.decode(errors="replace")
    out = []
    for line in stdout.splitlines():
        if line.startswith("PARTIAL "):
            try:
                out.append(json.loads(line[len("PARTIAL "):]))
            except ValueError:
                pass
    return out


def _run_one(name: str) -> int:
    """Subprocess entry: run ONE metric, print its JSON. Under --profile
    (BENCH_PROFILE=1, inherited from the parent) the stack sampler runs
    across the metric's trials and the top hotspot stacks per trace root
    ride along under `hotspots`; the result is flagged `profiled` so
    --compare refuses to score it against an unprofiled baseline."""
    import jax

    enable_compile_cache()
    if os.environ.get("BENCH_PROFILE") != "1":
        print(json.dumps(_METRICS[name](jax)))
        return 0
    from lighthouse_tpu.metrics.profiler import StackProfiler

    prof = StackProfiler()
    prof.start()
    try:
        result = _METRICS[name](jax)
    finally:
        prof.stop()
    result["hotspots"] = prof.top_stacks(n=5)
    result["profile"] = {"hz": prof.hz, "samples": prof.samples_total}
    result["profiled"] = True
    print(json.dumps(result))
    return 0


def main():
    # Hard wall-clock budget (BENCH_BUDGET_S, default 20 min — the driver's
    # kill window ate round 3's 50-min default). Each metric runs in a
    # subprocess sharing the persistent compile cache. The driver parses the
    # LAST complete JSON line of the tail, so this loop prints a well-formed
    # combined line after EVERY metric completes: a kill at any point leaves
    # the best result so far on stdout instead of erasing finished work.
    # Cheap secondaries run first; the BLS headline runs last with whatever
    # budget remains and, when it completes, takes over the final line.
    import subprocess

    _refuse_sanitize_mode()
    budget = float(os.environ.get("BENCH_BUDGET_S", "1200"))
    deadline = time.monotonic() + budget
    details = []
    errors = {}

    def run_metric(name: str, cap: float):
        # budget exhaustion first: a cap that went non-positive only
        # because the deadline passed is NOT an explicit env-var skip
        remaining = deadline - time.monotonic()
        if remaining <= 30:
            errors[name] = "skipped: budget exhausted"
            return None
        if cap <= 0:
            errors[name] = (
                f"skipped: BENCH_TIMEOUT_{name.upper()}=0 (explicitly disabled)"
            )
            return None
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--metric", name],
                capture_output=True,
                text=True,
                timeout=min(cap, remaining),
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired as e:
            # keep whatever per-trial/per-chunk timings completed: a timed-out
            # metric still yields data instead of a bare error string
            partial = _collect_partials(e.stdout)
            msg = f"timed out (> {min(cap, remaining):.0f}s)"
            errors[name] = {"error": msg, "partial": partial} if partial else msg
            return None
        if proc.returncode != 0:
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            msg = f"exit {proc.returncode}: {' | '.join(tail)}"
            # a crashed metric (OOM kill, assert) salvages its completed
            # trial/chunk timings exactly like a timed-out one
            partial = _collect_partials(proc.stdout)
            errors[name] = {"error": msg, "partial": partial} if partial else msg
            return None
        try:
            # last stdout line is the metric JSON (warnings may precede)
            return json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            errors[name] = f"unparseable output: {proc.stdout[-200:]!r}"
            return None

    def emit(head):
        """Print the combined line for the results gathered so far."""
        out = dict(head)
        out["details"] = [d for d in details if d is not head]
        if errors:
            out["errors"] = dict(errors)
        if os.environ.get("BENCH_PROFILE") == "1":
            # profiled trials carry sampling overhead (bounded ≤1.10× by
            # perf_smoke, but real): flag the whole line so --compare and
            # baseline rebasing skip it, like the sanitize-mode exclusion
            out["profiled"] = True
        print(json.dumps(out), flush=True)

    secondary_caps = {
        "merkle": 180,
        "pairing": 60,  # host microbench, no compiles
        "block_import": 90,
        # 1M-registry genesis (~15 s) + untimed park to the boundary + 3
        # cold productions (each pays the boundary transition inline) +
        # one pre-advance + 3 pre-advanced productions;
        # BENCH_TIMEOUT_BLOCK_PRODUCTION overrides (0 = explicit skip)
        "block_production": 420,
        "epoch_transition": 120,
        # 1M-validator fixture build (~15 s) + columns cold build + 3
        # resident trials + the subsampled legacy-oracle control;
        # BENCH_TIMEOUT_EPOCH_TRANSITION_1M overrides (0 = explicit skip)
        "epoch_transition_1m": 420,
        "state_root": 300,  # 1M-validator build + 3 cold columnar rebuilds
        "epoch_reroot": 300,  # 1M mass-churn full-rebuild re-roots
        "kzg": 240,  # metric 4; compile served by the warmed cache
        # 768-cell build is disk-cached after the first run; 3 batched
        # trials + 2 scalar-oracle subsample controls + tamper parity
        "da_verify": 300,
        # two-regime withholding fleet scenario (refusal->finality,
        # >=50%->reconstruction import); fake_crypto, no compiles
        "da_withholding": 300,
        "sync_catchup": 120,  # fake_crypto loopback pair; no compiles
        # 3 flood trials (2 flooder services each) + 3 flood-free
        # controls; fake_crypto, no compiles
        "gossip_soak": 180,
        # N-node fleet boot + healthy soak epochs + partition-heal
        # cycles with convergence waits; fake_crypto, no compiles
        "testnet_soak": 300,
        # 16k-validator fixture + 3 columnar trials + 2 scalar-oracle
        # controls (the controls dominate: ~65k per-validator Python
        # iterations each)
        "attestation_batch": 120,
        # 1M-vote columnar rounds are ~150 ms; the 1/16-subsample scalar
        # oracle (62.5k dict-walked votes per round) dominates
        "fork_choice": 120,
        # 500k-attestation pool build (~20 s of insert-time hashing) + 3
        # flat packs + the 31k-candidate rescan reference controls
        "op_pool": 240,
        # 2x 2048-aggregate flood build + 3 pre-warmed engines (one warm
        # epoch each) + 3 timed flood cycles + 2 scalar-subsample
        # controls; BENCH_TIMEOUT_SLASHER_INGEST overrides (0 = skip)
        "slasher_ingest": 240,
        # 1M fixture build + 3 cold full-table assemblies + 2 full-table
        # per-object oracle controls (those dominate) + hot/paginated
        # sweeps + the workers={1,4} forked-replica axis (two server
        # boots, HTTP load, full-table digest bursts, a head-refresh
        # rotation); BENCH_TIMEOUT_API_THROUGHPUT overrides (0 = skip)
        "api_throughput": 540,
        # pure-host fan-out: 1k/10k subscriber phases at paced cadence +
        # the per-subscriber serialization control + the eviction phase;
        # BENCH_TIMEOUT_SSE_FANOUT overrides (0 = skip)
        "sse_fanout": 180,
        # 100k-key fixture + registration + one full epoch of REAL host
        # BLS batch signing (~32 fixed-base tables + 100k window muls)
        # + the 1/64 per-key-oracle control (generic pt_mul dominates);
        # BENCH_TIMEOUT_VC_EPOCH_100K overrides (0 = explicit skip)
        "vc_epoch_100k": 600,
        # 8-epoch source chain + 3 checkpoint boots (3 HTTP round-trips
        # each) + a full backfill per trial; fake_crypto, no compiles
        "checkpoint_boot_s": 180,
        # two 10-epoch harness chains (migration ON + OFF control),
        # hot-store byte sampling per epoch; fake_crypto, no compiles
        "store_soak": 240,
        # disk-backed fleet boot + finality warmup + kill/restart rounds
        # with reconvergence waits; fake_crypto, no compiles
        "testnet_churn_soak": 420,
    }
    for name, cap in secondary_caps.items():
        cap = _metric_cap(name, cap)
        result = run_metric(name, cap=min(cap, deadline - time.monotonic()))
        if result is not None:
            details.append(result)
            emit(details[0])  # provisional headline: first survivor

    head = run_metric(
        "bls", cap=_metric_cap("bls", deadline - time.monotonic())
    )
    if head is None and not details:
        head = {"metric": "bench_failed", "value": 0, "unit": "",
                "vs_baseline": 0}
    emit(head if head is not None else details[0])


def _load_bench_entries(path: str) -> tuple[dict, bool]:
    """Flatten one bench JSON (a combined line, or a driver BENCH_rXX.json
    wrapper whose `parsed` holds it) into {metric: entry}; second element
    reports whether the run was profiled (not comparable)."""
    with open(path) as f:
        raw = json.load(f)
    if "parsed" in raw and isinstance(raw["parsed"], dict):
        raw = raw["parsed"]
    entries: dict[str, dict] = {}

    def add(e):
        if (
            isinstance(e, dict)
            and isinstance(e.get("metric"), str)
            and isinstance(e.get("value"), (int, float))
        ):
            entries[e["metric"]] = e
            # axis sub-metrics (e.g. api_throughput_workers{1,4}) compare
            # individually — each carries its own unit for direction
            for s in e.get("sub_metrics", ()):
                if isinstance(s, dict):
                    add(s)

    add(raw)
    for d in raw.get("details", ()):
        add(d)
    profiled = bool(raw.get("profiled")) or any(
        e.get("profiled") for e in entries.values()
    )
    return entries, profiled


def _rel_spread(entry: dict) -> float:
    """(max-min)/median of a metric's trial spread — its noise floor.
    Metrics without a recorded spread (e.g. block_import_ms) report 0
    and fall back to the bare threshold."""
    s = entry.get("spread")
    if not isinstance(s, dict):
        return 0.0
    try:
        med = float(s["median_s"])
        return (float(s["max_s"]) - float(s["min_s"])) / med if med else 0.0
    except (KeyError, TypeError, ValueError, ZeroDivisionError):
        return 0.0


# Explicit per-metric regression directions, consulted BEFORE the unit
# heuristic below. Slope/size metrics need this: store_soak's unit is
# "bytes/epoch" — the "/s"-style probes can't classify it, and a growth
# slope regresses UP no matter how its unit reads. True = higher is
# better, False = lower is better; metrics not listed fall back to the
# unit heuristic.
_METRIC_DIRECTIONS = {
    "checkpoint_boot_s": False,  # boot latency
    "store_soak": False,  # final hot-store bytes, migration ON
    "store_soak_migration_off": False,  # control (migration OFF)
    "testnet_churn_soak": True,  # finalization throughput under churn
    "testnet_churn_hot_growth": False,  # bounded-store multiple
}


def _higher_is_better(unit: str) -> bool:
    # throughputs count up: "leaves/sec", "cells/s (…)", and testnet_soak's
    # "slots finalized per wall-second" — the padded "/s " probe matches a
    # bare "/s" mid- or end-of-string without catching "ms/…" latencies
    u = (unit or "") + " "
    return "/sec" in u or "/s " in u or "per wall-second" in u


def compare_runs(old_path: str, new_path: str, threshold: float = 0.15) -> int:
    """`bench.py --compare OLD.json NEW.json`: the regression sentinel.
    For every metric present in both files, compute the regression
    fraction in the metric's own direction (throughputs regress down,
    latencies regress up) and flag it when it exceeds
    max(threshold, (old_spread + new_spread) / 2) — spread-aware, so a
    metric whose own trials wobble 20% needs a >20% move to fire.
    Prints a per-metric delta table; exits 1 on any REGRESSED metric,
    2 when either side is a profiled (non-comparable) run."""
    old, old_prof = _load_bench_entries(old_path)
    new, new_prof = _load_bench_entries(new_path)
    if old_prof or new_prof:
        which = " and ".join(
            p for p, flag in ((old_path, old_prof), (new_path, new_prof)) if flag
        )
        print(
            f"refusing to compare: {which} recorded under --profile "
            "(sampler overhead rides the numbers; re-run without it)"
        )
        return 2
    shared = [m for m in old if m in new]
    if not shared:
        print(f"no shared metrics between {old_path} and {new_path}")
        return 2
    rows = []
    regressed = []
    for m in sorted(shared):
        o, n = old[m], new[m]
        ov, nv = float(o["value"]), float(n["value"])
        if ov == 0:
            rows.append((m, ov, nv, "n/a", "n/a", "SKIP (old=0)"))
            continue
        direction = _METRIC_DIRECTIONS.get(m)
        higher = (
            direction
            if direction is not None
            else _higher_is_better(n.get("unit") or o.get("unit") or "")
        )
        # regression fraction, positive = worse in this metric's direction
        r = (ov - nv) / ov if higher else (nv - ov) / ov
        tol = max(threshold, (_rel_spread(o) + _rel_spread(n)) / 2.0)
        if r > tol:
            verdict = "REGRESSED"
            regressed.append(m)
        elif -r > tol:
            verdict = "improved"
        else:
            verdict = "ok"
        delta_pct = (nv - ov) / ov * 100.0
        rows.append(
            (m, ov, nv, f"{delta_pct:+.1f}%", f"±{tol * 100:.0f}%", verdict)
        )
    widths = [max(len(str(r[i])) for r in rows + [("metric", "old", "new",
               "delta", "tolerance", "verdict")]) for i in range(6)]
    header = ("metric", "old", "new", "delta", "tolerance", "verdict")
    for row in [header] + rows:
        print("  ".join(str(c).ljust(w) for c, w in zip(row, widths)))
    only_old = sorted(set(old) - set(new))
    only_new = sorted(set(new) - set(old))
    if only_old:
        print(f"only in {old_path}: {', '.join(only_old)}")
    if only_new:
        print(f"only in {new_path}: {', '.join(only_new)}")
    if regressed:
        print(f"REGRESSION: {', '.join(regressed)} "
              f"(median worse by more than the spread-aware threshold)")
        return 1
    print(f"ok: {len(shared)} shared metrics within threshold")
    return 0


def _refuse_sanitize_mode():
    """Sanitize mode write-guards buffers and runs wide-dtype checks on
    every sweep — numbers recorded under it are not comparable to the
    baselines (BENCH_NOTES.md "Sanitize mode"). Refuse, loudly."""
    if os.environ.get("LIGHTHOUSE_TPU_SANITIZE") == "1":
        print(
            json.dumps(
                {
                    "error": (
                        "refusing to record timed trials with "
                        "LIGHTHOUSE_TPU_SANITIZE=1 set — sanitize mode is "
                        "excluded from benchmarks; unset it and re-run"
                    )
                }
            )
        )
        sys.exit(2)


def _parse_args(argv: list[str]) -> list[str]:
    """Strip --bls-backend / --profile (both propagated via env to the
    metric subprocesses)."""
    out = []
    i = 0
    while i < len(argv):
        if argv[i] == "--bls-backend":
            if i + 1 >= len(argv):
                raise SystemExit("--bls-backend requires a value (host|tpu)")
            os.environ["BENCH_BLS_BACKEND"] = argv[i + 1]
            i += 2
        elif argv[i].startswith("--bls-backend="):
            os.environ["BENCH_BLS_BACKEND"] = argv[i].split("=", 1)[1]
            i += 1
        elif argv[i] == "--profile":
            os.environ["BENCH_PROFILE"] = "1"
            i += 1
        else:
            out.append(argv[i])
            i += 1
    return out


if __name__ == "__main__":
    argv = _parse_args(sys.argv[1:])
    if argv and argv[0] == "--compare":
        # pure file comparison: no metrics run, sanitize mode irrelevant.
        # Bad arity must ERROR, not fall through into a full bench run
        if len(argv) != 3:
            raise SystemExit("usage: bench.py --compare OLD.json NEW.json")
        sys.exit(compare_runs(argv[1], argv[2]))
    # covers the --metric subprocess entry too: no timed trial ever runs
    # with the sanitizer's guards armed
    _refuse_sanitize_mode()
    if len(argv) == 2 and argv[0] == "--metric":
        sys.exit(_run_one(argv[1]))
    sys.exit(main())
