"""The system under test: a beacon node booted from an anchor state, fed
gossip bytes the way its gossip router feeds them, and watched from outside.

Nothing here changes what the node does. Observers are attached to the
running node's own objects and taken off again at `stop`: one records
every gossip verdict the router reaches, one times each device batch
verification (its sets and its answer). A traffic kind may attach more
through `wrap`.
"""

from __future__ import annotations

import sys
import threading
import time
from dataclasses import dataclass, field

import jax


@dataclass
class DeviceCall:
    start: float
    end: float
    sets: int
    keys: int
    messages: int
    signatures: list
    verdict: bool


@dataclass
class Observed:
    """Everything the observers saw, under one lock."""

    lock: threading.Lock = field(default_factory=threading.Lock)
    done: threading.Condition = None
    verdicts: dict = field(default_factory=dict)  # raw bytes -> (kind, time)
    calls: list = field(default_factory=list)
    batches: list = field(default_factory=list)  # (start, end, n)

    def __post_init__(self):
        self.done = threading.Condition(self.lock)


def verdict_kind(err) -> str:
    from lighthouse_tpu.network import GossipIgnore

    if err is None:
        return "accept"
    if isinstance(err, GossipIgnore):
        return "ignore"
    if isinstance(err, ValueError):
        return "reject"
    return "error"


def device_counters() -> dict:
    """The program's own counts of device batch verifications and sets."""
    from lighthouse_tpu.metrics import REGISTRY

    return {k: REGISTRY.counter(k).value() for k in ("bls_device_batches_total", "bls_device_sets_total")}


def boot(cfg: dict, backend: str, key_arr=None):
    """(node, stages): the registry, the anchor state, the anchor block's
    root as the benchmark computes it, and the node booted from the state,
    each stage timed."""
    from . import registry

    stages = {}
    t = time.perf_counter()
    if key_arr is None:
        key_arr = registry.keys(cfg)
    stages["registry_s"] = time.perf_counter() - t
    t = time.perf_counter()
    node_spec, E, state = registry.anchor_state(cfg, key_arr)
    stages["state_s"] = time.perf_counter() - t
    t = time.perf_counter()
    anchor_root = registry.anchor_root(cfg, state)
    stages["anchor_root_s"] = time.perf_counter() - t
    t = time.perf_counter()
    node = Node(node_spec, E, state, backend)
    del state
    stages["boot_s"] = time.perf_counter() - t
    node.key_arr, node.anchor_root = key_arr, anchor_root
    return node, stages


class Node:
    def __init__(self, spec, E, state, backend: str):
        from lighthouse_tpu.client import ClientBuilder, ClientConfig

        self.config = ClientConfig(
            spec=spec,
            E=E,
            genesis_state=state,
            bls_backend=backend,
            kzg="none",
            http_port=None,
            network_port=0,
            sync_service_interval=None,
            manual_slot_clock=True,
        )
        self.client = ClientBuilder(self.config).build().start()
        self.chain = self.client.chain
        self.net = self.client.network
        self.head_root = self.chain.head_root
        self.seen = Observed()
        self._undo = []

    def wrap(self, owner, name: str, wrapper):
        """Replace owner.name by wrapper(owner.name) until `stop`."""
        own = name in vars(owner)
        original = getattr(owner, name)
        setattr(owner, name, wrapper(original))
        self._undo.append((owner, name, original if own else None))

    # -- observers -------------------------------------------------------

    def observe_verdicts(self):
        """Record every gossip verdict the router reaches: raw -> (kind, time)."""
        seen = self.seen

        def completes(complete):
            def on_complete(work, err):
                kind = verdict_kind(err)
                now = time.perf_counter()
                with seen.lock:
                    seen.verdicts[work.data] = (kind, now)
                    seen.done.notify_all()
                complete(work, err)

            return on_complete

        self.wrap(self.net.gossip, "_complete", completes)

    def observe_device_calls(self, backend: str):
        """Time each batch verification the BLS backend sends to the device
        (one per chunk of sets) and keep its sets and its answer. Every
        module that holds the verifier under its name is rebound, so the
        calls are seen wherever the program imports it from; a check
        compares their number with the program's own counter."""
        from lighthouse_tpu.crypto import bls as node_bls

        if backend == "tpu":
            from lighthouse_tpu.ops import bls381_verify

            verifier = bls381_verify.verify_signature_sets_device_full
            owners = [
                m for m in list(sys.modules.values())
                if getattr(m, "__name__", "").startswith("lighthouse_tpu")
                and getattr(m, verifier.__name__, None) is verifier
            ]
            name = verifier.__name__
        else:  # the tests' stand-in for the device: the host backend's batch
            owners, name = [node_bls._BACKENDS[backend]], "verify_signature_sets"
        seen = self.seen

        def verifies(verify):
            def on_verify(sets, rng=None):
                sets = list(sets)
                keys = max(len(s.pubkeys) for s in sets) if sets else 0
                msgs = len({s.message for s in sets})
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(
                    f"bench.verify_call#sets={len(sets)},keys={keys},msgs={msgs}#"
                ):
                    ok = verify(sets, rng)
                t1 = time.perf_counter()
                with seen.lock:
                    seen.calls.append(
                        DeviceCall(t0, t1, len(sets), keys, msgs,
                                   [s.signature.to_bytes() for s in sets], bool(ok))
                    )
                return ok

            return on_verify

        for owner in owners:
            self.wrap(owner, name, verifies)

    # -- driving ---------------------------------------------------------

    def set_slot(self, slot: int):
        self.client.slot_clock.set_slot(slot)
        # the slot edge's own work (state pre-advance, reprocess expiry)
        # runs now, in set-up, and not inside the measured window
        self.net.slot_tick()
        if not self.net.processor.drain(timeout=600):
            raise RuntimeError("slot tick work did not finish")

    def deliver(self, topic: str, raw: bytes):
        """One gossip message into the router's queue-routed path: decode on
        this (reader) thread, process on the beacon processor's lane."""
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.net.gossip._deliver(topic, raw, "bench-peer")

    def settle(self, timeout: float) -> bool:
        """Wait until the beacon processor has nothing queued or running:
        every message submitted so far has its verdict, or never will."""
        return self.net.processor.drain(timeout=timeout)

    def stop(self):
        self.client.stop()
        for owner, name, original in reversed(self._undo):
            if original is None:
                delattr(owner, name)
            else:
                setattr(owner, name, original)
        self._undo.clear()
