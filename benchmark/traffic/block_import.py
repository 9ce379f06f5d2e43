"""Traffic kind `block_import`: full blocks and their blob sidecars on the
gossip topics, one block in flight, imported by a node with the device
KZG engine on the mainnet trusted setup.

The mix's parameters: `blocks` (the chain: one block a slot from slot 1,
transition.py), `participation` (each aggregate's seeded share of its
committee, and the sync aggregate's), `blobs` (the blob pool every block
carries), `warmup_blocks` (imported in set-up, before the window),
`tamper_first` and `tamper_last` (the window block before which the
tampered copy goes, picked by the seed). The chain depends on the
configuration alone; the seed picks the tampered block and its swapped
aggregate signature.

Loop: closed, one block outstanding. For each block the slot clock is set
to its slot, the block goes on the beacon_block topic and its sidecars
right after it on the blob sidecar topic, through the router's queue-
routed path; the next block goes in once this one has its verdict. Each
block and each sidecar is a message. A sidecar's verdict is its gossip
verification (the program's `process_blob_sidecars`, header signature and
KZG proof); a block's is its import or its rejection (`process_block`
returning, or raising anything but "waiting on its sidecars"). Before
one of the window's blocks, a copy with one aggregate's signature swapped
for another's, re-signed by its proposer, goes in with its own sidecars:
it passes gossip and the availability gate, and import must reject it in
the signature batch; the valid block of that slot follows on gossip as
the next block (the program's gossip path has no first-block-per-proposer
rule, so gossip is the route it takes).

Reference (transition.py, kzg.py): the valid chain's blocks are imported,
the tampered one is rejected (the reference's own BLS finds its swapped
signature invalid), every sidecar is accepted (its proof is the unique
valid one for its blob), the head after each import is the reference's
root of that block, and the node's final head state has the reference's
post-state root.

Numbers compared, each with its limit:
  verdict_mismatch              verdicts that differ from the reference's
  head_mismatch                 imports after which the head is not the
                                benchmark's root of that block
  state_root_mismatch           the node's final head state's root (the
                                benchmark's SSZ) is not the reference's
  kzg_verifications_unobserved  |blob proofs the program counted
                                (kzg_blob_verifications_total) - sidecars
                                delivered|
  device_batches_unobserved     device batch verifications the program
                                counted that the observer did not see, or
                                the other way round
  missing_verdicts              messages delivered without a verdict
  traffic_exhausted             the window outlived the chain
  anchor_root_mismatch          the node's head at boot is not the
                                benchmark's anchor block
  tampered_rejected             tampered blocks rejected: at least 1
  blocks_compared               blocks given a verdict: at least 1
"""

from __future__ import annotations

import gc
import random
import threading
import time

import jax

from benchmark import registry, transition
from benchmark.node import Node, device_counters
from benchmark.traffic.gossip_attestations import device_checks

#: how long after the window closes the run waits for the block in flight
LATE_S = 120.0
#: how long a warm-up block may take (the first run in a checkout compiles)
WARMUP_S = 1100.0
#: the program's blob-proof counter (a program without it cannot be checked)
KZG_COUNTER = "kzg_blob_verifications_total"
KZG_SETUPS = {"mainnet": "default"}


class ImportNode(Node):
    """node.Node with a KZG engine: ClientBuilder builds the device one on
    the configuration's trusted setup when the BLS backend is the tpu."""

    def __init__(self, spec, E, state, backend: str, kzg_setup: str):
        from lighthouse_tpu.client import ClientBuilder, ClientConfig

        from benchmark.node import Observed

        self.config = ClientConfig(
            spec=spec, E=E, genesis_state=state, bls_backend=backend,
            kzg=KZG_SETUPS[kzg_setup], http_port=None, network_port=0,
            sync_service_interval=None, manual_slot_clock=True,
        )
        self.client = ClientBuilder(self.config).build().start()
        self.chain = self.client.chain
        self.net = self.client.network
        self.head_root = self.chain.head_root
        self.seen = Observed()
        self._undo = []


def kzg_counter() -> float:
    from lighthouse_tpu.metrics import REGISTRY

    return REGISTRY.counter(KZG_COUNTER).value()


def require_program():
    """Refuse a program that does not count its blob proofs: the check
    `kzg_verifications_unobserved` could not read it."""
    import lighthouse_tpu.beacon_chain.data_availability  # noqa: F401 — registers
    from lighthouse_tpu.metrics import REGISTRY

    if KZG_COUNTER not in REGISTRY.expose():
        raise RuntimeError(f"the program has no {KZG_COUNTER} counter: it cannot run this cell")


class Cell:
    def __init__(self, cfg, mix, seed, backend="tpu", fault=None):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.backend, self.fault = backend, fault
        self.node = None
        self.lock = threading.Condition()
        self.block_verdicts: dict = {}  # block signature -> (kind, time, head after)
        self.sidecar_verdicts: dict = {}  # (header signature, index) -> (kind, time)
        self.delivered: list = []  # (kind, key, t, window) per message
        self.block_spans: list = []
        self.exhausted = False

    # -- set-up -----------------------------------------------------------------

    def setup(self, stages: dict):
        require_program()
        cfg, mix = self.cfg, self.mix
        t = time.perf_counter()
        key_arr = registry.keys(cfg)
        stages["registry_s"] = time.perf_counter() - t
        t = time.perf_counter()
        node_spec, E, state = registry.anchor_state(cfg, key_arr)
        stages["state_s"] = time.perf_counter() - t
        t = time.perf_counter()
        anchor_root = registry.anchor_root(cfg, state)
        stages["anchor_root_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.chain = transition.build_chain(cfg, mix, state, anchor_root)
        stages["chain_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.node = ImportNode(node_spec, E, state, self.backend, cfg["kzg_setup"])
        del state
        self.node.key_arr, self.node.anchor_root = key_arr, anchor_root
        stages["boot_s"] = time.perf_counter() - t
        node = self.node
        if self.fault is not None:
            self.fault(node)
        self.counters_setup = device_counters()
        self.kzg_setup = kzg_counter()
        node.observe_device_calls(self.backend)
        node.wrap(node.chain, "process_block", self._observe_block)
        node.wrap(node.chain, "process_blob_sidecars", self._observe_sidecars)

        # the tampered copy of one window block, picked by the seed
        t = time.perf_counter()
        rng = random.Random(f"{self.seed}:tamper")
        self.tamper_at = rng.randint(mix["tamper_first"], mix["tamper_last"]) - 1
        entry = self.chain.blocks[self.tamper_at]
        swap = rng.randrange(len(entry["signed"]["message"]["body"]["attestations"]))
        self.tampered = transition.tampered(cfg, self.chain, self.tamper_at, swap)
        stages["traffic_s"] = time.perf_counter() - t

        # every key the chain's signatures name, in the node's pubkey cache
        t = time.perf_counter()
        self.committees = committees = transition.committees_of(cfg)
        used = {int(v) for s in range(mix["blocks"]) for c in range(committees.per_slot)
                for v in committees.committee(s, c)}
        used |= set(self.chain.sync_indices)
        used |= {b["signed"]["message"]["proposer_index"] for b in self.chain.blocks}
        stages["pubkey_cache_misses"] = registry.load_pubkey_cache(key_arr, sorted(used))
        stages["pubkeys_s"] = time.perf_counter() - t

        # warm-up: the first blocks through the same path, every program
        # shape the window uses (a block's signature chunks, the sidecars'
        # header checks and KZG proofs, the state root, the store)
        t = time.perf_counter()
        for entry in self.chain.blocks[: mix["warmup_blocks"]]:
            self._offer(entry, window=False)
            if not self._wait(entry, time.perf_counter() + WARMUP_S):
                raise RuntimeError(f"warm-up block {entry['slot']} got no verdict")
        stages["warmup_s"] = time.perf_counter() - t
        # the generator and the reference share the node's process: their
        # set-up garbage (the 1M-validator reference state, the chain) is
        # collected here, on set-up's clock, and not in the window
        t = time.perf_counter()
        gc.collect()
        stages["gc_s"] = time.perf_counter() - t

    # -- observers ---------------------------------------------------------------

    def _observe_block(self, process_block):
        from lighthouse_tpu.beacon_chain.chain import BlobsUnavailableError

        def on_process_block(block_input, *args, **kwargs):
            signed = getattr(block_input, "signed_block", block_input)
            key = bytes(signed.signature)
            try:
                out = process_block(block_input, *args, **kwargs)
            except BlobsUnavailableError:
                raise  # staged: the verdict comes with its last sidecar
            except ValueError:
                self._record_block(key, "reject")
                raise
            except Exception:
                self._record_block(key, "error")
                raise
            self._record_block(key, "import")
            return out

        return on_process_block

    def _record_block(self, key, kind):
        now = time.perf_counter()
        head = bytes(self.node.chain.head_root)
        with self.lock:
            self.block_verdicts.setdefault(key, (kind, now, head))
            self.lock.notify_all()

    def _observe_sidecars(self, process):
        from lighthouse_tpu.beacon_chain.chain import BlobsUnavailableError

        def on_sidecars(block_root, sidecars, *args, **kwargs):
            keys = [(bytes(sc.signed_block_header.signature), int(sc.index)) for sc in sidecars]
            kind = "accept"
            try:
                return process(block_root, sidecars, *args, **kwargs)
            except BlobsUnavailableError:
                kind = "ignore"
                raise
            except ValueError:
                kind = "reject"
                raise
            except Exception:
                kind = "error"
                raise
            finally:
                now = time.perf_counter()
                with self.lock:
                    for k in keys:
                        self.sidecar_verdicts.setdefault(k, (kind, now))
                    self.lock.notify_all()

        return on_sidecars

    # -- offering -----------------------------------------------------------------

    def _offer(self, entry: dict, window: bool):
        """The block on its topic, then its sidecars on theirs."""
        node = self.node
        node.set_slot(entry["slot"])
        t = time.perf_counter()
        sig = entry["signed"]["signature"]
        with self.lock:
            self.delivered.append(("block", sig, t, window))
            self.delivered.extend(("sidecar", (sig, i), t, window)
                                  for i in range(len(entry["sidecars"])))
        node.deliver(node.net.topic_block, entry["raw"])
        for raw in entry["sidecars"]:
            node.deliver(node.net.topic_blob_sidecar, raw)
        entry["offered_at"] = t

    def _wait(self, entry: dict, deadline: float) -> bool:
        """Until the block has its verdict or the deadline passes."""
        key = entry["signed"]["signature"]
        with self.lock:
            while key not in self.block_verdicts:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self.lock.wait(min(left, 0.05))
            verdict_t = self.block_verdicts[key][1]
        self.block_spans.append(("bench.block_import", entry["offered_at"], verdict_t))
        return True

    def window(self, t0: float, t_end: float):
        self.t0, self.t_end = t0, t_end
        self.counters_window = device_counters()
        queue = []
        for i in range(self.mix["warmup_blocks"], len(self.chain.blocks)):
            if i == self.tamper_at:
                queue.append(self.tampered)
            queue.append(self.chain.blocks[i])
        self.pending = None
        for entry in queue:
            if time.perf_counter() >= t_end:
                break
            with jax.profiler.TraceAnnotation("bench.block_import"):
                self._offer(entry, window=True)
                if not self._wait(entry, t_end):
                    self.pending = entry  # in flight at the window's end
                    break
        else:
            self.exhausted = time.perf_counter() < t_end
        self.counters_end = device_counters()

    def close(self):
        if self.pending is not None:
            self._wait(self.pending, time.perf_counter() + LATE_S)
        self.node.settle(LATE_S)
        self.counters_close = device_counters()
        self.kzg_close = kzg_counter()
        with self.node.seen.lock:
            self.calls = list(self.node.seen.calls)

    def stop(self):
        if self.node is not None:
            self.node.stop()
            self.head_root = self.node.head_root
            self.head_state = self.node.chain.head_state
            self.final_head = bytes(self.node.chain.head_root)
            self.node = None

    # -- the reference ------------------------------------------------------------

    def check(self) -> dict:
        from benchmark import kzg

        chain = self.chain
        expected = {b["signed"]["signature"]: ("import", b["root"]) for b in chain.blocks}
        # the tampered block and the valid one it copies, by the
        # reference's own BLS: the valid one carries no invalid signature,
        # the tampered one does
        view = transition.signing_view(chain, self.tamper_at)
        committees = self.committees
        for signed, root in ((self.tampered["signed"], self.tampered["root"]),
                             (chain.blocks[self.tamper_at]["signed"],
                              chain.blocks[self.tamper_at]["root"])):
            sets = transition.signature_sets(self.cfg, view, signed, committees,
                                             chain.sync_indices)
            ok = all(transition.sets_valid(sets))
            expected[signed["signature"]] = ("import" if ok else "reject", root)
        sidecar_ok = all(kzg.proof_valid(b, c, p, chain.tau) for b, c, p in chain.blobs)

        mismatch = head_mismatch = missing = compared = tampered_rejected = 0
        for kind, key, _t, _w in self.delivered:
            if kind == "block":
                got = self.block_verdicts.get(key)
                if got is None:
                    missing += 1
                    continue
                compared += 1
                want, root = expected[key]
                mismatch += got[0] != want
                head_mismatch += got[0] == "import" and got[2] != root
                if key == self.tampered["signed"]["signature"] and got[0] == "reject":
                    tampered_rejected += 1
            else:
                got = self.sidecar_verdicts.get(key)
                if got is None:
                    missing += 1
                    continue
                mismatch += got[0] != ("accept" if sidecar_ok else "reject")
        roots = {b["root"]: b["state_root"] for b in chain.blocks}
        roots[chain.anchor_root] = chain.anchor_state_root
        want_root = roots.get(self.final_head)
        got_root = transition.state_root_of(
            self.head_state, self.cfg["SLOTS_PER_EPOCH"], self.cfg["SHUFFLE_ROUND_COUNT"])
        sidecars = sum(1 for kind, *_ in self.delivered if kind == "sidecar")
        if self.backend == "tpu":
            counted = (self.counters_close["bls_device_batches_total"]
                       - self.counters_setup["bls_device_batches_total"])
        else:  # the host backend, the tests' stand-in for the device, counts nothing
            counted = len(self.calls)
        self.compared = {"compared_blocks": compared,
                         "compared_messages": len(self.delivered) - missing}
        self.failed = int(mismatch + missing)
        return {
            "verdict_mismatch": {"value": int(mismatch), "limit": 0},
            "head_mismatch": {"value": int(head_mismatch), "limit": 0},
            "state_root_mismatch": {"value": int(got_root != want_root), "limit": 0},
            "kzg_verifications_unobserved": {
                "value": int(abs(self.kzg_close - self.kzg_setup - sidecars)), "limit": 0},
            "device_batches_unobserved": device_checks(counted, len(self.calls), 1)[
                "device_batches_unobserved"],
            "missing_verdicts": {"value": missing, "limit": 0},
            "traffic_exhausted": {"value": int(self.exhausted), "limit": 0},
            "anchor_root_mismatch": {"value": int(self.head_root != chain.anchor_root),
                                     "limit": 0},
            "tampered_rejected": {"value": tampered_rejected, "least": 1},
            "blocks_compared": {"value": compared, "least": 1},
        }

    def record(self) -> dict:
        in_window = 0
        for kind, key, _t, window in self.delivered:
            if not window:
                continue
            got = (self.block_verdicts if kind == "block" else self.sidecar_verdicts).get(key)
            in_window += got is not None and self.t0 <= got[1] <= self.t_end
        imported = sum(1 for kind, t, _h in self.block_verdicts.values()
                       if kind == "import" and self.t0 <= t <= self.t_end)
        return {
            "attempted": sum(1 for *_, window in self.delivered if window),
            "failed": self.failed,
            "in_window": in_window,
            "calls": self.calls,
            "host_spans": list(self.block_spans),
            "counters": (self.counters_window, self.counters_end),
            "compared": self.compared,
            "blocks_imported": imported,
            "sidecars_per_block": len(self.chain.blobs),
        }
