"""Traffic kind `gossip_attestations`: unaggregated attestations on the
attestation subnets, offered in a closed loop.

The mix's parameters: `invalid_one_in` (one message in so many carries a
signature by the right validator over another message, the next
committee's data: the middle one of each run of that many in arrival
order), `outstanding` (messages kept in the node), `burst` (the loop
sends new messages in bursts of this many, one burst as soon as that many
have their verdicts), `warmup_full` and `warmup_partial` (warm-up batch
sizes). Everything else comes from `--seed`, so the same seed gives the
same messages, and every seed gives the same number of messages, the same
invalid positions in arrival order and the same committee sizes: only the
slot, the validators, which messages are invalid and the arrival order
change.

Generator: every attestation of one slot, all committees, as the SSZ bytes
a node receives. Signing uses sk_v = s0 + v: with H = hash_to_g2(m),
sig_v = s0*H + (v mod 1024)*H + (v div 1024)*1024*H, two table look-ups
and two additions per signature (see bls.py and registry.py).

Reference: a plain reading of the gossip rules for an unaggregated
attestation, on the benchmark's own decoder, shuffle, signing roots and
curve arithmetic: the message must decode, set exactly one bit of its
committee, name the anchor block (whose root the benchmark computes
itself, spec.py) as head and target, and carry the signature its
committee member's key gives over the attestation's signing root. The
reference knows every secret key, and a BLS signature is unique for a key
and a message, so "valid" is "equal to sk_v * H(signing root)", computed
by a plain double-and-add, not by the generator's tables.

Numbers compared, each with its limit:
  verdict_mismatch           verdicts that differ from the reference's
                             (accept for valid, reject for invalid; ignore
                             or an internal error is a mismatch either way)
  batch_mismatch             device batch verifications whose answer
                             differs from "every set in it is valid"
  missing_verdicts           messages submitted that never got a verdict
  traffic_exhausted          the window ran out of the slot's messages
  anchor_root_mismatch       the node's head at boot is not the anchor
                             block the benchmark built
  device_batches_unobserved  device batch verifications the program
                             counted (bls_device_batches_total) that the
                             observer did not see, or the other way round
  device_batches_compared    device batches compared: at least one
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import random
import time
from dataclasses import dataclass

import jax

from benchmark import bls, registry, spec
from benchmark.node import boot, device_counters

#: processes that sign a slot's messages in set-up (they never touch JAX)
SIGNING_WORKERS = 8
#: the pause after each burst of the closed loop
BURST_GAP_S = 0.001
#: how long after the window closes the run waits for outstanding verdicts
LATE_S = 60.0
#: how long a warm-up batch may take (the first run in a checkout compiles)
WARMUP_S = 1100.0
#: most messages the reference re-derives in one run; beyond it a seeded
#: sample of device batches, with every message in them and every message
#: the generator made invalid
MAX_COMPARED = 4096


@dataclass
class Message:
    raw: bytes  # SSZ Attestation
    topic_index: int  # attestation subnet
    valid: bool  # what the generator meant; the reference decides


def _attestation_data(cfg, slot, index, anchor_root) -> bytes:
    epoch = slot // cfg["SLOTS_PER_EPOCH"]
    return spec.attestation_data_bytes(
        slot, index, anchor_root, (0, bytes(32)), (epoch, anchor_root)
    )


def domain(cfg) -> bytes:
    return spec.compute_domain(
        spec.DOMAIN_BEACON_ATTESTER,
        bytes.fromhex(cfg["fork_version"]),
        bytes.fromhex(cfg["genesis_validators_root"]),
    )


def subnet(cfg, per_slot: int, slot: int, index: int) -> int:
    since_epoch_start = per_slot * (slot % cfg["SLOTS_PER_EPOCH"])
    return (since_epoch_start + index) % cfg["ATTESTATION_SUBNET_COUNT"]


def committees(cfg) -> spec.Committees:
    return spec.Committees(
        cfg["validators"], cfg["SLOTS_PER_EPOCH"], cfg["MAX_COMMITTEES_PER_SLOT"],
        cfg["TARGET_COMMITTEE_SIZE"], cfg["SHUFFLE_ROUND_COUNT"],
        spec.attester_seed(0, bytes.fromhex(cfg["randao_mix"])),
    )


# --- generator ---------------------------------------------------------------


class _Signer:
    """(s0 + v) * H for one message H and many v < 2^20."""

    def __init__(self, root: bytes, s0: int):
        h = bls.hash_to_g2(root)
        (h_aff,) = bls.g2_to_affine_many([h])
        low = [bls.G2_INF]
        for _ in range(1023):
            low.append(bls.g2_add_affine(low[-1], h_aff))
        (h1024,) = bls.g2_to_affine_many([bls.g2_mul(h, 1024)])
        high = [bls.G2_INF]
        for _ in range(1023):
            high.append(bls.g2_add_affine(high[-1], h1024))
        self.low = [None] + bls.g2_to_affine_many(low[1:])
        self.high = [None] + bls.g2_to_affine_many(high[1:])
        self.base = bls.g2_mul(h, s0)

    def sign_many(self, vs: list) -> list:
        out = []
        for v in vs:
            acc = self.base
            lo, hi = v & 1023, v >> 10
            if lo:
                acc = bls.g2_add_affine(acc, self.low[lo])
            if hi:
                acc = bls.g2_add_affine(acc, self.high[hi])
            out.append(acc)
        return [bls.g2_compress(a) for a in bls.g2_to_affine_many(out)]


def _sign_job(job) -> list:
    root, s0, vs = job
    return _Signer(root, s0).sign_many(vs)


def attestation_slot(cfg, mix, committees, anchor_root, slot, seed) -> list:
    """Every attestation of `slot`, signed, in the seed's arrival order."""
    rng = random.Random(f"{seed}:arrivals")
    s0 = int(cfg["key_base"], 16)
    per_slot = committees.per_slot
    dom = domain(cfg)
    data = [_attestation_data(cfg, slot, c, anchor_root) for c in range(per_slot)]
    roots = [spec.signing_root(spec.attestation_data_root(d), dom) for d in data]
    members = [committees.committee(slot, c).tolist() for c in range(per_slot)]
    order = [(c, p) for c in range(per_slot) for p in range(len(members[c]))]
    rng.shuffle(order)
    every = mix["invalid_one_in"]
    invalid = set(range(every // 2, len(order), every))
    # which (committee, position) signs which committee's message
    signed_over = {}
    for i, (c, p) in enumerate(order):
        signed_over[(c, p)] = (c + 1) % per_slot if i in invalid else c
    jobs: dict[int, list] = {}
    for (c, p), m in signed_over.items():
        jobs.setdefault(m, []).append((c, p))
    sigs = {}
    work = [(roots[m], s0, [members[c][p] for c, p in cps]) for m, cps in jobs.items()]
    ctx = multiprocessing.get_context("spawn")
    workers = max(1, min(SIGNING_WORKERS, os.cpu_count() or 1, len(work)))
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        for cps, signed in zip(jobs.values(), pool.map(_sign_job, work)):
            sigs.update(zip(cps, signed))
    return [
        Message(
            raw=spec.attestation_bytes(len(members[c]), p, data[c], sigs[(c, p)]),
            topic_index=subnet(cfg, per_slot, slot, c),
            valid=i not in invalid,
        )
        for i, (c, p) in enumerate(order)
    ]


def warmup_messages(cfg, committees, anchor_root, slot, count, seed) -> list:
    """`count` attestations of another slot (validators that never attest
    in the measured slot), one of them invalid, signed one by one."""
    rng = random.Random(f"{seed}:warmup")
    s0 = int(cfg["key_base"], 16)
    dom = domain(cfg)
    spread = min(8, committees.per_slot)
    out = []
    hashes = {}
    for i in range(count):
        c = i % spread
        members = committees.committee(slot, c)
        p = i // spread
        d = _attestation_data(cfg, slot, c, anchor_root)
        bad = i == count // 2
        m = spec.attestation_data_root(
            _attestation_data(cfg, slot, (c + 1) % committees.per_slot, anchor_root) if bad else d
        )
        root = spec.signing_root(m, dom)
        if root not in hashes:
            hashes[root] = bls.hash_to_g2(root)
        v = int(members[p])
        sig = bls.g2_compress(bls.g2_to_affine_many([bls.g2_mul(hashes[root], s0 + v)])[0])
        out.append(
            Message(
                raw=spec.attestation_bytes(len(members), p, d, sig),
                topic_index=subnet(cfg, committees.per_slot, slot, c),
                valid=not bad,
            )
        )
    rng.shuffle(out)
    return out


# --- reference ---------------------------------------------------------------


def reference_valid(cfg, committees, anchor_root, slots, raw: bytes, hashes: dict) -> bool:
    try:
        positions, length, data, sig = spec.parse_attestation(raw)
    except ValueError:
        return False
    slot = int.from_bytes(data[0:8], "little")
    index = int.from_bytes(data[8:16], "little")
    epoch = slot // cfg["SLOTS_PER_EPOCH"]
    if (
        slot not in slots
        or index >= committees.per_slot
        or data[16:48] != anchor_root
        or int.from_bytes(data[88:96], "little") != epoch
        or data[96:128] != anchor_root
        or len(positions) != 1
    ):
        return False
    members = committees.committee(slot, index)
    if length != len(members):
        return False
    v = int(members[positions[0]])
    root = spec.signing_root(spec.attestation_data_root(data), domain(cfg))
    if root not in hashes:
        hashes[root] = bls.hash_to_g2(root)
    want = bls.g2_mul(hashes[root], (int(cfg["key_base"], 16) + v) % bls.R)
    return bls.g2_compress(bls.g2_to_affine_many([want])[0]) == sig


def compare(cfg, committees, anchor_root, slots, submitted, labels, verdicts, calls, seed) -> dict:
    """`submitted`: raw messages given to the node; `labels`: raw -> the
    generator's intent (used only to keep invalid ones in a sample);
    `verdicts`: raw -> (kind, time); `calls`: observed device batches."""
    missing = [r for r in submitted if r not in verdicts]
    by_sig = {spec.parse_attestation(r)[3]: r for r in submitted}
    if len(submitted) - len(missing) > MAX_COMPARED:
        rng = random.Random(f"{seed}:sample")
        chosen, budget = [], MAX_COMPARED
        for call in rng.sample(calls, len(calls)):
            if chosen and call.sets > budget:
                continue
            chosen.append(call)
            budget -= call.sets
        raws = {by_sig.get(s) for call in chosen for s in call.signatures}
        raws |= {r for r in submitted if not labels[r] and r in verdicts}
        raws.discard(None)
    else:
        chosen = list(calls)
        raws = {r for r in submitted if r in verdicts}
    hashes: dict = {}
    ref = {r: reference_valid(cfg, committees, anchor_root, slots, r, hashes) for r in raws}
    verdict_mismatch = sum(
        r in verdicts and verdicts[r][0] != ("accept" if ok else "reject") for r, ok in ref.items()
    )
    batch_mismatch = 0
    for call in chosen:
        oks = [ref.get(by_sig.get(s)) for s in call.signatures]
        # a set that is not one of the submitted messages is a mismatch too
        batch_mismatch += None in oks or call.verdict != all(oks)
    return {
        "verdict_mismatch": verdict_mismatch,
        "batch_mismatch": batch_mismatch,
        "missing_verdicts": len(missing),
        "compared_messages": len(ref),
        "compared_invalid": sum(not ok for ok in ref.values()),
        "compared_batches": len(chosen),
    }


def device_checks(counted: int, observed: int, compared: int) -> dict:
    """The device batches the program counted against those observed and
    compared: a verifier the observer cannot see reads as not correct."""
    return {
        "device_batches_unobserved": {"value": int(abs(counted - observed)), "limit": 0},
        "device_batches_compared": {"value": compared, "least": 1},
    }


# --- the cell ----------------------------------------------------------------


class Cell:
    def __init__(self, cfg, mix, seed, backend="tpu", fault=None):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.backend, self.fault = backend, fault
        self.node = None

    def setup(self, stages: dict):
        cfg, mix, seed = self.cfg, self.mix, self.seed
        self.node, boot_stages = boot(cfg, self.backend)
        stages.update(boot_stages)
        node = self.node
        if self.fault is not None:
            self.fault(node)
        self.counters_setup = device_counters()
        node.observe_verdicts()
        node.observe_device_calls(self.backend)
        node.wrap(node.chain, "process_attestation_batch", self._annotate_batches)

        t = time.perf_counter()
        self.committees = committees(cfg)
        self.anchor_root = node.anchor_root
        self.slot = 2 + random.Random(f"{seed}:slot").randrange(cfg["SLOTS_PER_EPOCH"] - 3)
        node.set_slot(self.slot)
        self.msgs = attestation_slot(cfg, mix, self.committees, self.anchor_root, self.slot, seed)
        self.warm = warmup_messages(
            cfg, self.committees, self.anchor_root, self.slot - 1,
            mix["warmup_full"] + mix["warmup_partial"], seed,
        )
        stages["traffic_s"] = time.perf_counter() - t

        # every key this run's traffic names, in the node's pubkey cache
        t = time.perf_counter()
        used = [int(v) for c in range(self.committees.per_slot)
                for v in self.committees.committee(self.slot, c)]
        used += [int(v) for c in range(min(8, self.committees.per_slot))
                 for v in self.committees.committee(self.slot - 1, c)]
        stages["pubkey_cache_misses"] = registry.load_pubkey_cache(node.key_arr, used)
        stages["pubkeys_s"] = time.perf_counter() - t

        # warm-up: a full processor batch (with an invalid message, so the
        # per-item fallback runs too), then a partial one: every program
        # shape and path the window can use
        t = time.perf_counter()
        full = mix["warmup_full"]
        for part in (self.warm[:full], self.warm[full:]):
            for m in part:
                node.deliver(self.topic(m), m.raw)
            node.settle(WARMUP_S)
        stages["warmup_s"] = time.perf_counter() - t

    def _annotate_batches(self, batch):
        seen = self.node.seen

        def on_batch(atts):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.attestation_batch"):
                out = batch(atts)
            with seen.lock:
                seen.batches.append((t0, time.perf_counter(), len(atts)))
            return out

        return on_batch

    def topic(self, m: Message) -> str:
        return self.node.net.attestation_topics[m.topic_index]

    def window(self, t0: float, t_end: float):
        """Closed loop: keep `outstanding` messages in the node, in arrival
        order, sent in bursts of `burst`: one burst as soon as that many
        have their verdicts."""
        node, msgs, seen = self.node, self.msgs, self.node.seen
        self.t0, self.t_end = t0, t_end
        self.counters_window = device_counters()
        outstanding, burst = self.mix["outstanding"], self.mix["burst"]
        self.exhausted = False
        with seen.lock:
            base = len(seen.verdicts)
        sent = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            with seen.lock:
                free = len(seen.verdicts) - base + outstanding - sent
                if free < burst:
                    seen.done.wait(min(0.05, t_end - now))
                    free = len(seen.verdicts) - base + outstanding - sent
            if free < burst:
                continue
            if sent + burst > len(msgs):
                self.exhausted = True  # the loop could not keep its messages outstanding
            for m in msgs[sent : sent + burst]:
                node.deliver(self.topic(m), m.raw)
            sent = min(sent + burst, len(msgs))
            # a pause between bursts, as between two reads from the network:
            # the node takes each burst in before the next one comes
            time.sleep(BURST_GAP_S)
        self.sent = sent
        self.counters_end = device_counters()

    def close(self):
        self.node.settle(LATE_S)
        self.counters_close = device_counters()
        seen = self.node.seen
        with seen.lock:
            self.verdicts = dict(seen.verdicts)
            self.calls = list(seen.calls)
            self.batches = list(seen.batches)

    def stop(self):
        if self.node is not None:
            self.node.stop()
            self.head_root = self.node.head_root
            self.node = None

    def check(self) -> dict:
        submitted = [m.raw for m in self.warm] + [m.raw for m in self.msgs[: self.sent]]
        labels = {m.raw: m.valid for m in self.msgs + self.warm}
        self.compared = compare(
            self.cfg, self.committees, self.anchor_root, {self.slot, self.slot - 1},
            submitted, labels, self.verdicts, self.calls, self.seed,
        )
        c = self.compared
        if self.backend == "tpu":
            counted = (self.counters_close["bls_device_batches_total"]
                       - self.counters_setup["bls_device_batches_total"])
        else:  # the host backend, the tests' stand-in for the device, counts nothing
            counted = len(self.calls)
        return {
            "verdict_mismatch": {"value": c["verdict_mismatch"], "limit": 0},
            "batch_mismatch": {"value": c["batch_mismatch"], "limit": 0},
            "missing_verdicts": {"value": c["missing_verdicts"], "limit": 0},
            "traffic_exhausted": {"value": int(self.exhausted), "limit": 0},
            "anchor_root_mismatch": {"value": int(self.head_root != self.anchor_root), "limit": 0},
            **device_checks(counted, len(self.calls), c["compared_batches"]),
        }

    def record(self) -> dict:
        submitted = self.msgs[: self.sent]
        in_window = sum(
            1 for m in submitted if m.raw in self.verdicts and self.verdicts[m.raw][1] <= self.t_end
        )
        return {
            "attempted": len(submitted),
            "failed": self.compared["verdict_mismatch"] + self.compared["missing_verdicts"],
            "in_window": in_window,
            "calls": self.calls,
            "host_spans": [("bench.verify_call", c.start, c.end) for c in self.calls]
            + [("bench.attestation_batch", a, b) for a, b, _ in self.batches],
            "counters": (self.counters_window, self.counters_end),
            "compared": {k: v for k, v in self.compared.items() if k.startswith("compared_")},
        }
