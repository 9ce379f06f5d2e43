"""The plain reference for full-block import: a Deneb state transition for
the blocks of one epoch, and the chain of full blocks it builds.

Straight from consensus-specs v1.4.0 (phase0, altair, bellatrix, capella
and deneb beacon-chain.md, mainnet preset), importing nothing of the
program under test: `process_slots` within an epoch and `process_block`
(header, withdrawals sweep, execution payload, RANDAO, eth1 vote,
attestations with participation flags and the proposer's reward, the sync
aggregate's rewards and penalties), over numpy columns of the registry,
with the state root from the benchmark's own SSZ (deneb.py) and an
incremental tree per large field; the validators subtree does not change
inside an epoch without deposits, exits or slashings. Signatures are the
benchmark's own BLS (bls.py): the reference knows every secret key,
sk_v = key_base + v, and a BLS signature is unique for a key and a
message, so a signature set is valid exactly when its signature equals
(sum of its secret keys) * H(message). Blob proofs are kzg.py's.

The chain: one block a slot from slot 1 of the anchor's epoch, none
across its boundary. Block s carries an aggregate of every committee of
slots s-1 and s-2 (a seeded `participation` share of its members), a sync
aggregate, the blob pool's commitments, and an execution payload carrying
one blob transaction with their versioned hashes. Everything depends on
the configuration alone, so the chain and its post-state roots are built
once per checkout under benchmark/.cache/.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import math
import multiprocessing
import os
import pickle
import random

import numpy as np

from . import bls, deneb, kzg, spec
from .deneb import Tree
from .keccak import blob_transaction, execution_block_hash

DOMAIN_BEACON_PROPOSER = bytes.fromhex("00000000")
DOMAIN_BEACON_ATTESTER = bytes.fromhex("01000000")
DOMAIN_RANDAO = bytes.fromhex("02000000")
DOMAIN_SYNC_COMMITTEE = bytes.fromhex("07000000")
EFFECTIVE_BALANCE_INCREMENT = 10**9
MAX_EFFECTIVE_BALANCE = 32 * 10**9
BASE_REWARD_FACTOR = 64
WEIGHTS = (14, 26, 14)  # timely source, target, head
SYNC_REWARD_WEIGHT, PROPOSER_WEIGHT, WEIGHT_DENOMINATOR = 2, 8, 64
SLOTS_PER_HISTORICAL_ROOT = 8192
EPOCHS_PER_HISTORICAL_VECTOR = 65536
EPOCHS_PER_ETH1_VOTING_PERIOD = 64
MIN_SEED_LOOKAHEAD = 1
MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP = 16384
MAX_WITHDRAWALS_PER_PAYLOAD = 16
MAX_BLOBS_PER_BLOCK = 6
GAS_PER_BLOB = 131072
TARGET_BLOB_GAS_PER_BLOCK = 393216
SECONDS_PER_SLOT = 12
CHAIN_ID = 1
#: processes that sign a block's aggregates (they never touch JAX)
SIGNING_WORKERS = 8
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")

_STATE_FIELDS = [name for name, _ in spec.BEACON_STATE_DENEB]
_STATE_TYPES = dict(spec.BEACON_STATE_DENEB)


def u64(v: int) -> bytes:
    return int(v).to_bytes(8, "little")


def _chunk(v: int) -> bytes:
    return u64(v).ljust(32, b"\x00")


# --- committees, proposers, the sync committee ----------------------------------


def compute_shuffled_index(index: int, count: int, seed: bytes, rounds: int) -> int:
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(spec.sha256(seed + rb)[:8], "little") % count
        flip = (pivot + count - index) % count
        position = max(index, flip)
        source = spec.sha256(seed + rb + (position // 256).to_bytes(4, "little"))
        if (source[(position % 256) // 8] >> (position % 8)) & 1:
            index = flip
    return index


def get_seed(mixes: Tree, epoch: int, domain_type: bytes) -> bytes:
    mix = mixes.levels[0][(epoch + EPOCHS_PER_HISTORICAL_VECTOR - MIN_SEED_LOOKAHEAD - 1)
                          % EPOCHS_PER_HISTORICAL_VECTOR]
    return spec.sha256(domain_type + u64(epoch) + mix)


def _balance_weighted_pick(eb, seed: bytes, start: int, count: int, rounds: int,
                           per_index_seed) -> int:
    """compute_proposer_index / the sync committee's sampling: the i-th
    shuffled candidate, kept when a random byte admits its balance."""
    i = start
    while True:
        candidate = compute_shuffled_index(i % count, count, seed, rounds)
        random_byte = per_index_seed(i)
        if int(eb[candidate]) * 255 >= MAX_EFFECTIVE_BALANCE * random_byte:
            return candidate, i
        i += 1


def proposer_index(st, slot: int) -> int:
    seed = spec.sha256(get_seed(st.mixes, slot // st.slots_per_epoch, DOMAIN_BEACON_PROPOSER)
                       + u64(slot))
    proposer, _ = _balance_weighted_pick(
        st.eb, seed, 0, st.n, st.rounds,
        lambda i: spec.sha256(seed + u64(i // 32))[i % 32],
    )
    return proposer


def sync_committee_indices(st, epoch: int, size: int) -> list:
    """altair get_next_sync_committee_indices for `epoch`."""
    seed = get_seed(st.mixes, epoch, DOMAIN_SYNC_COMMITTEE)
    out, i = [], 0
    while len(out) < size:
        candidate, i = _balance_weighted_pick(
            st.eb, seed, i, st.n, st.rounds,
            lambda j: spec.sha256(seed + u64(j // 32))[j % 32],
        )
        out.append(candidate)
        i += 1
    return out


# --- the state ----------------------------------------------------------------------


def _plain(typ, value):
    """A program container's value in deneb.py's form (dicts and lists)."""
    if isinstance(typ, list):
        return {name: _plain(t, getattr(value, name)) for name, t in typ}
    if typ[0] == "uint":
        return int(value)
    if typ[0] in ("bytes", "bytelist"):
        return bytes(value)
    if typ[0] in ("bits", "bitlist"):
        return [int(bool(b)) for b in value]
    return [_plain(typ[1], v) for v in value]


def validators_root(validators) -> bytes:
    """hash_tree_root(List[Validator, 2^40]) of the program's validators."""
    sha = spec.sha256
    leaves = []
    for v in validators:
        pk = bytes(v.pubkey)
        left = sha(sha(sha(pk[:32] + pk[32:].ljust(32, b"\x00")) + bytes(v.withdrawal_credentials))
                   + sha(_chunk(v.effective_balance) + _chunk(int(bool(v.slashed)))))
        right = sha(sha(_chunk(v.activation_eligibility_epoch) + _chunk(v.activation_epoch))
                    + sha(_chunk(v.exit_epoch) + _chunk(v.withdrawable_epoch)))
        leaves.append(sha(left + right))
    return deneb.mix_in_length(spec.merkleize(leaves, spec.REGISTRY_LIMIT), len(leaves))


def _packed_tree(arr: np.ndarray, limit_items: int) -> Tree:
    data = arr.tobytes()
    data += bytes(-len(data) % 32)
    per_chunk = 32 // arr.itemsize
    return Tree([data[i : i + 32] for i in range(0, len(data), 32)],
                (limit_items + per_chunk - 1) // per_chunk)


class RefState:
    """A Deneb BeaconState inside one epoch, as the reference keeps it."""

    @classmethod
    def from_program(cls, state, slots_per_epoch: int, rounds: int) -> "RefState":
        """Read the program's state object as data (the anchor, or the
        node's head state for the final comparison)."""
        st = cls()
        st.slots_per_epoch, st.rounds = slots_per_epoch, rounds
        small = ("genesis_time", "genesis_validators_root", "slot", "fork",
                 "latest_block_header", "eth1_data", "eth1_data_votes",
                 "eth1_deposit_index", "justification_bits", "previous_justified_checkpoint",
                 "current_justified_checkpoint", "finalized_checkpoint",
                 "current_sync_committee", "next_sync_committee",
                 "latest_execution_payload_header", "next_withdrawal_index",
                 "next_withdrawal_validator_index")
        st.small = {name: _plain(_STATE_TYPES[name], getattr(state, name)) for name in small}
        st.fixed_roots = {
            name: deneb.hash_tree_root(_STATE_TYPES[name], list(getattr(state, name)))
            for name in ("historical_roots", "slashings", "historical_summaries")
        }
        validators = state.validators
        st.n = len(validators)
        st.fixed_roots["validators"] = validators_root(validators)
        st.eb = np.fromiter((v.effective_balance for v in validators), np.uint64, st.n)
        st.slashed = np.fromiter((bool(v.slashed) for v in validators), np.bool_, st.n)
        st.activation = np.fromiter((v.activation_epoch for v in validators), np.uint64, st.n)
        st.exit = np.fromiter((v.exit_epoch for v in validators), np.uint64, st.n)
        st.withdrawable = np.fromiter((v.withdrawable_epoch for v in validators), np.uint64, st.n)
        st.credential_prefix = np.fromiter(
            (bytes(v.withdrawal_credentials)[0] for v in validators), np.uint8, st.n
        )
        st.balances = np.fromiter(state.balances, np.uint64, st.n)
        st.balance_tree = _packed_tree(st.balances, spec.REGISTRY_LIMIT)
        st.participation = np.fromiter(state.current_epoch_participation, np.uint8, st.n)
        st.participation_tree = _packed_tree(st.participation, spec.REGISTRY_LIMIT)
        for name in ("previous_epoch_participation", "inactivity_scores"):
            dtype = np.uint8 if _STATE_TYPES[name][1] == spec.U8 else np.uint64
            arr = np.fromiter(getattr(state, name), dtype, st.n)
            st.fixed_roots[name] = deneb.mix_in_length(
                _packed_tree(arr, spec.REGISTRY_LIMIT).root(), len(arr))
        st.block_roots = Tree([bytes(r) for r in state.block_roots], SLOTS_PER_HISTORICAL_ROOT)
        st.state_roots = Tree([bytes(r) for r in state.state_roots], SLOTS_PER_HISTORICAL_ROOT)
        st.mixes = Tree([bytes(r) for r in state.randao_mixes], EPOCHS_PER_HISTORICAL_VECTOR)
        st.last_root = None
        st._total_active = None
        return st

    # -- accessors --------------------------------------------------------------

    @property
    def slot(self) -> int:
        return self.small["slot"]

    @property
    def epoch(self) -> int:
        return self.slot // self.slots_per_epoch

    def block_root_at(self, slot: int) -> bytes:
        if not slot < self.slot <= slot + SLOTS_PER_HISTORICAL_ROOT:
            raise ValueError("slot out of range")
        return self.block_roots.levels[0][slot % SLOTS_PER_HISTORICAL_ROOT]

    def mix(self, epoch: int) -> bytes:
        return self.mixes.levels[0][epoch % EPOCHS_PER_HISTORICAL_VECTOR]

    def total_active_balance(self) -> int:
        """Constant inside the epoch the reference covers."""
        if self._total_active is None:
            active = (self.activation <= self.epoch) & (self.epoch < self.exit)
            self._total_active = max(EFFECTIVE_BALANCE_INCREMENT, int(self.eb[active].sum()))
        return self._total_active

    def base_reward_per_increment(self) -> int:
        return EFFECTIVE_BALANCE_INCREMENT * BASE_REWARD_FACTOR // math.isqrt(
            self.total_active_balance())

    def domain(self, domain_type: bytes) -> bytes:
        return spec.compute_domain(domain_type, self.small["fork"]["current_version"],
                                   self.small["genesis_validators_root"])

    def set_balance(self, index: int, value: int):
        self.balances[index] = value
        self.balance_tree.set(index // 4, self.balances[index - index % 4 : index - index % 4 + 4]
                              .tobytes().ljust(32, b"\x00"))

    def increase_balance(self, index: int, delta: int):
        self.set_balance(index, int(self.balances[index]) + delta)

    def decrease_balance(self, index: int, delta: int):
        self.set_balance(index, max(0, int(self.balances[index]) - delta))

    # -- the root -----------------------------------------------------------------

    def field_roots(self) -> list:
        roots = []
        for name in _STATE_FIELDS:
            if name in self.small:
                roots.append(deneb.hash_tree_root(_STATE_TYPES[name], self.small[name]))
            elif name in self.fixed_roots:
                roots.append(self.fixed_roots[name])
            elif name == "block_roots":
                roots.append(self.block_roots.root())
            elif name == "state_roots":
                roots.append(self.state_roots.root())
            elif name == "randao_mixes":
                roots.append(self.mixes.root())
            elif name == "balances":
                roots.append(deneb.mix_in_length(self.balance_tree.root(), self.n))
            elif name == "current_epoch_participation":
                roots.append(deneb.mix_in_length(self.participation_tree.root(), self.n))
            else:
                raise KeyError(name)
        return roots

    def root(self) -> bytes:
        return spec.merkleize(self.field_roots())


def state_root_of(state, slots_per_epoch: int = 32, rounds: int = 90) -> bytes:
    """hash_tree_root of a program BeaconState (Deneb), by this module's
    own SSZ."""
    return RefState.from_program(state, slots_per_epoch, rounds).root()


# --- process_slots -----------------------------------------------------------------


def process_slots(st: RefState, slot: int):
    while st.slot < slot:
        if (st.slot + 1) % st.slots_per_epoch == 0:
            raise NotImplementedError("the reference covers the slots of one epoch")
        previous_state_root = st.last_root if st.last_root is not None else st.root()
        st.state_roots.set(st.slot % SLOTS_PER_HISTORICAL_ROOT, previous_state_root)
        header = st.small["latest_block_header"]
        if header["state_root"] == bytes(32):
            header["state_root"] = previous_state_root
        st.block_roots.set(st.slot % SLOTS_PER_HISTORICAL_ROOT,
                           deneb.hash_tree_root(deneb.HEADER, header))
        st.small["slot"] += 1
        st.last_root = None


# --- process_block -----------------------------------------------------------------


class Invalid(ValueError):
    """The block breaks a rule of the state transition."""


def _require(cond, what):
    if not cond:
        raise Invalid(what)


def expected_withdrawals(st: RefState) -> list:
    """capella get_expected_withdrawals."""
    out = []
    index = st.small["next_withdrawal_index"]
    v = st.small["next_withdrawal_validator_index"]
    for _ in range(min(st.n, MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP)):
        balance = int(st.balances[v])
        eth1 = st.credential_prefix[v] == 1
        if eth1 and st.withdrawable[v] <= st.epoch and balance > 0:
            amount = balance
        elif eth1 and st.eb[v] == MAX_EFFECTIVE_BALANCE and balance > MAX_EFFECTIVE_BALANCE:
            amount = balance - MAX_EFFECTIVE_BALANCE
        else:
            amount = 0
        if amount:
            raise NotImplementedError("the reference's payloads carry no withdrawals")
        v = (v + 1) % st.n
    return out


def process_withdrawals(st: RefState, payload: dict):
    expected = expected_withdrawals(st)
    _require(payload["withdrawals"] == expected, "withdrawals differ from the sweep")
    if len(expected) == MAX_WITHDRAWALS_PER_PAYLOAD:
        raise NotImplementedError("a full withdrawals payload")
    st.small["next_withdrawal_validator_index"] = (
        st.small["next_withdrawal_validator_index"] + MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP
    ) % st.n


def engine_accepts(payload: dict, versioned_hashes: list, parent_beacon_block_root: bytes) -> bool:
    """What the deployment's execution engine checks before VALID: the
    block hash, and that the blob transactions carry exactly the block's
    versioned hashes (the only transaction form this reference builds)."""
    return (
        execution_block_hash(payload, parent_beacon_block_root) == payload["block_hash"]
        and payload["transactions"]
        == [blob_transaction(CHAIN_ID, payload["block_number"], versioned_hashes)]
    )


def process_execution_payload(st: RefState, block: dict):
    body = block["body"]
    payload = body["execution_payload"]
    header = st.small["latest_execution_payload_header"]
    _require(payload["parent_hash"] == header["block_hash"], "payload parent hash")
    _require(payload["prev_randao"] == st.mix(st.epoch), "payload prev_randao")
    _require(payload["timestamp"] == st.small["genesis_time"] + st.slot * SECONDS_PER_SLOT,
             "payload timestamp")
    _require(len(body["blob_kzg_commitments"]) <= MAX_BLOBS_PER_BLOCK, "too many blobs")
    hashes = [kzg.versioned_hash(c) for c in body["blob_kzg_commitments"]]
    _require(engine_accepts(payload, hashes, block["parent_root"]), "execution engine")
    new = {name: payload[name] for name, _ in spec.PAYLOAD_HEADER if name in payload}
    types = dict(deneb.EXECUTION_PAYLOAD)
    new["transactions_root"] = deneb.hash_tree_root(types["transactions"], payload["transactions"])
    new["withdrawals_root"] = deneb.hash_tree_root(types["withdrawals"], payload["withdrawals"])
    st.small["latest_execution_payload_header"] = new


def participation_flags(st: RefState, data: dict, inclusion_delay: int) -> list:
    """altair/deneb get_attestation_participation_flag_indices."""
    current = data["target"]["epoch"] == st.epoch
    justified = st.small["current_justified_checkpoint" if current
                         else "previous_justified_checkpoint"]
    source = data["source"] == justified
    target = source and data["target"]["root"] == st.block_root_at(
        data["target"]["epoch"] * st.slots_per_epoch)
    head = target and data["beacon_block_root"] == st.block_root_at(data["slot"])
    _require(source, "attestation source")
    flags = []
    if source and inclusion_delay <= math.isqrt(st.slots_per_epoch):
        flags.append(0)
    if target:
        flags.append(1)
    if head and inclusion_delay == 1:
        flags.append(2)
    return flags


def process_attestation(st: RefState, att: dict, committees, proposer: int):
    data = att["data"]
    epoch = st.epoch
    _require(data["target"]["epoch"] in (max(0, epoch - 1), epoch), "target epoch")
    _require(data["target"]["epoch"] == data["slot"] // st.slots_per_epoch, "target slot")
    _require(data["slot"] + 1 <= st.slot, "inclusion delay")
    _require(data["index"] < committees.per_slot, "committee index")
    _require(data["target"]["epoch"] == epoch, "the reference's attestations are current")
    committee = committees.committee(data["slot"], data["index"])
    bits = np.array(att["aggregation_bits"], dtype=bool)
    _require(len(bits) == len(committee) and bits.any(), "aggregation bits")
    flags = participation_flags(st, data, st.slot - data["slot"])
    idx = committee[bits]
    base = (st.eb[idx] // EFFECTIVE_BALANCE_INCREMENT).astype(object) * st.base_reward_per_increment()
    old = st.participation[idx]
    new = old.copy()
    numerator = 0
    for flag in flags:
        fresh = (old >> flag) & 1 == 0
        numerator += int(sum(base[fresh])) * WEIGHTS[flag]
        new[fresh] |= np.uint8(1 << flag)
    st.participation[idx] = new
    for c in np.unique(idx // 32):
        st.participation_tree.set(int(c), st.participation[32 * c : 32 * c + 32].tobytes()
                                  .ljust(32, b"\x00"))
    denominator = (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT) * WEIGHT_DENOMINATOR // PROPOSER_WEIGHT
    st.increase_balance(proposer, numerator // denominator)


def process_sync_aggregate(st: RefState, aggregate: dict, sync_indices: list, proposer: int):
    total_increments = st.total_active_balance() // EFFECTIVE_BALANCE_INCREMENT
    total_base_rewards = st.base_reward_per_increment() * total_increments
    max_participant_rewards = (total_base_rewards * SYNC_REWARD_WEIGHT // WEIGHT_DENOMINATOR
                               // st.slots_per_epoch)
    participant_reward = max_participant_rewards // len(sync_indices)
    proposer_reward = participant_reward * PROPOSER_WEIGHT // (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT)
    for index, bit in zip(sync_indices, aggregate["sync_committee_bits"]):
        if bit:
            st.increase_balance(index, participant_reward)
            st.increase_balance(proposer, proposer_reward)
        else:
            st.decrease_balance(index, participant_reward)


def process_block(st: RefState, block: dict, committees, sync_indices: list):
    """Apply `block` to `st` in place (signatures are checked apart, by
    `signature_sets`); raises Invalid on a broken rule."""
    body = block["body"]
    header = st.small["latest_block_header"]
    _require(block["slot"] == st.slot and block["slot"] > header["slot"], "block slot")
    proposer = proposer_index(st, st.slot)
    _require(block["proposer_index"] == proposer, "proposer index")
    _require(block["parent_root"] == deneb.hash_tree_root(deneb.HEADER, header), "parent root")
    st.small["latest_block_header"] = {
        "slot": block["slot"], "proposer_index": proposer, "parent_root": block["parent_root"],
        "state_root": bytes(32), "body_root": deneb.hash_tree_root(deneb.BODY, body),
    }
    _require(not st.slashed[proposer], "proposer slashed")
    process_withdrawals(st, body["execution_payload"])
    process_execution_payload(st, block)
    epoch = st.epoch
    st.mixes.set(epoch % EPOCHS_PER_HISTORICAL_VECTOR, bytes(
        a ^ b for a, b in zip(st.mix(epoch), spec.sha256(body["randao_reveal"]))))
    votes = st.small["eth1_data_votes"]
    votes.append(body["eth1_data"])
    if votes.count(body["eth1_data"]) * 2 > EPOCHS_PER_ETH1_VOTING_PERIOD * st.slots_per_epoch:
        st.small["eth1_data"] = body["eth1_data"]
    for name in ("proposer_slashings", "attester_slashings", "voluntary_exits",
                 "bls_to_execution_changes"):
        _require(not body[name], f"the reference's blocks carry no {name}")
    _require(len(body["deposits"]) == min(
        16, st.small["eth1_data"]["deposit_count"] - st.small["eth1_deposit_index"]), "deposits")
    for att in body["attestations"]:
        process_attestation(st, att, committees, proposer)
    process_sync_aggregate(st, body["sync_aggregate"], sync_indices, proposer)


# --- signatures --------------------------------------------------------------------


def secret_key(cfg: dict, v: int) -> int:
    return (int(cfg["key_base"], 16) + int(v)) % bls.R


def signature_sets(cfg, st_pre: RefState, signed: dict, committees, sync_indices) -> list:
    """(signing root, secret-key sum, signature) of every signature a
    block carries, against its pre-state (advanced to its slot)."""
    block, body = signed["message"], signed["message"]["body"]
    s0 = int(cfg["key_base"], 16)
    sets = []
    dom_att = st_pre.domain(DOMAIN_BEACON_ATTESTER)
    for att in body["attestations"]:
        committee = committees.committee(att["data"]["slot"], att["data"]["index"])
        members = committee[np.array(att["aggregation_bits"], dtype=bool)]
        root = spec.signing_root(deneb.hash_tree_root(deneb.ATTESTATION_DATA, att["data"]), dom_att)
        sets.append((root, (len(members) * s0 + int(members.astype(object).sum())) % bls.R,
                     att["signature"]))
    agg = body["sync_aggregate"]
    positions = [i for i, b in enumerate(agg["sync_committee_bits"]) if b]
    sync_root = spec.signing_root(st_pre.block_root_at(block["slot"] - 1),
                                  st_pre.domain(DOMAIN_SYNC_COMMITTEE))
    sets.append((sync_root, sum(secret_key(cfg, sync_indices[i]) for i in positions) % bls.R,
                 agg["sync_committee_signature"]))
    sk = secret_key(cfg, block["proposer_index"])
    epoch = block["slot"] // st_pre.slots_per_epoch
    sets.append((spec.signing_root(_chunk(epoch), st_pre.domain(DOMAIN_RANDAO)), sk,
                 body["randao_reveal"]))
    sets.append((spec.signing_root(deneb.hash_tree_root(deneb.BLOCK, block),
                                   st_pre.domain(DOMAIN_BEACON_PROPOSER)), sk, signed["signature"]))
    return sets


def _sign_job(job) -> list:
    root, scalars = job
    h = bls.hash_to_g2(root)
    points = bls.g2_to_affine_many([bls.g2_mul(h, k) for k in scalars])
    return [bls.g2_compress(p) for p in points]


def sign_many(jobs: list, pool=None) -> list:
    """[compressed signatures of each scalar] for each (root, scalars)."""
    return list(pool.map(_sign_job, jobs, chunksize=4)) if pool else [_sign_job(j) for j in jobs]


def sets_valid(sets: list, pool=None) -> list:
    """For each signature set, whether its signature is sk_sum * H(root)."""
    got = sign_many([(root, [k]) for root, k, _ in sets], pool)
    return [g[0] == sig for g, (_, _, sig) in zip(got, sets)]


# --- the chain ---------------------------------------------------------------------


def _seeded_bits(label: str, count: int, share: float) -> list:
    rng = random.Random(label)
    bits = [int(rng.random() < share) for _ in range(count)]
    if not any(bits):
        bits[rng.randrange(count)] = 1
    return bits


def _payload(st: RefState, slot: int, parent_root: bytes, commitments: list) -> dict:
    parent = st.small["latest_execution_payload_header"]
    number = parent["block_number"] + 1
    payload = {
        "parent_hash": parent["block_hash"],
        "fee_recipient": hashlib.sha256(b"fee recipient").digest()[:20],
        "state_root": hashlib.sha256(b"execution state" + u64(slot)).digest(),
        "receipts_root": hashlib.sha256(b"receipts" + u64(slot)).digest(),
        "logs_bloom": bytes(256),
        "prev_randao": st.mix(st.epoch),
        "block_number": number,
        "gas_limit": 30_000_000,
        "gas_used": 21_000,
        "timestamp": st.small["genesis_time"] + slot * SECONDS_PER_SLOT,
        "extra_data": b"tpu-beacon",
        "base_fee_per_gas": 7,
        "block_hash": bytes(32),
        "transactions": [blob_transaction(CHAIN_ID, number,
                                          [kzg.versioned_hash(c) for c in commitments])],
        "withdrawals": [],
        "blob_gas_used": GAS_PER_BLOB * len(commitments),
        "excess_blob_gas": max(0, parent["excess_blob_gas"] + parent["blob_gas_used"]
                               - TARGET_BLOB_GAS_PER_BLOCK),
    }
    payload["block_hash"] = execution_block_hash(payload, parent_root)
    return payload


def header_of(block: dict) -> dict:
    return {"slot": block["slot"], "proposer_index": block["proposer_index"],
            "parent_root": block["parent_root"], "state_root": block["state_root"],
            "body_root": deneb.hash_tree_root(deneb.BODY, block["body"])}


def sidecars_of(signed: dict, pool_blobs: list) -> list:
    """The SSZ BlobSidecar of each of the block's blobs."""
    block = signed["message"]
    signed_header = {"message": header_of(block), "signature": signed["signature"]}
    out = []
    for i, (blob, commitment, proof) in enumerate(pool_blobs):
        out.append(deneb.serialize(deneb.BLOB_SIDECAR, {
            "index": i, "blob": blob, "kzg_commitment": commitment, "kzg_proof": proof,
            "signed_block_header": signed_header,
            "kzg_commitment_inclusion_proof": deneb.commitment_inclusion_proof(block["body"], i),
        }))
    return out


def sign_block(cfg, st_pre: RefState, block: dict) -> dict:
    root = spec.signing_root(deneb.hash_tree_root(deneb.BLOCK, block),
                             st_pre.domain(DOMAIN_BEACON_PROPOSER))
    (sig,) = _sign_job((root, [secret_key(cfg, block["proposer_index"])]))
    return {"message": block, "signature": sig}


class Chain:
    """The built chain: per block its signed value, SSZ bytes, root, post-
    state root and field roots, sidecars; and what the reference needs to
    re-derive it (committees, sync committee, blob pool, [tau]G1)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _cache_path(cfg: dict, mix: dict) -> str:
    key = repr((sorted(cfg.items()), [(k, mix[k]) for k in sorted(mix) if k != "about"]))
    digest = hashlib.sha256(key.encode()).hexdigest()[:16]
    return os.path.join(CACHE_DIR, f"chain_{cfg['validators']}_{digest}.pkl")


def committees_of(cfg) -> spec.Committees:
    return spec.Committees(
        cfg["validators"], cfg["SLOTS_PER_EPOCH"], cfg["MAX_COMMITTEES_PER_SLOT"],
        cfg["TARGET_COMMITTEE_SIZE"], cfg["SHUFFLE_ROUND_COUNT"],
        spec.attester_seed(0, bytes.fromhex(cfg["randao_mix"])),
    )


def build_chain(cfg: dict, mix: dict, anchor_state, anchor_root: bytes) -> Chain:
    """The chain of `mix['blocks']` full blocks on the anchor, from the
    cache when this checkout built it before."""
    path = _cache_path(cfg, mix)
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    chain = _build(cfg, mix, anchor_state, anchor_root)
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(chain, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return chain


def anchor_reference(cfg: dict, anchor_state) -> RefState:
    return RefState.from_program(anchor_state, cfg["SLOTS_PER_EPOCH"], cfg["SHUFFLE_ROUND_COUNT"])


def _build(cfg, mix, anchor_state, anchor_root) -> Chain:
    st = anchor_reference(cfg, anchor_state)
    if not ((st.activation <= st.epoch) & (st.epoch < st.exit)).all():
        raise NotImplementedError("the committees (spec.Committees) assume every validator active")
    anchor_state_root = st.root()
    st.last_root = anchor_state_root
    committees = committees_of(cfg)
    size = cfg["SYNC_COMMITTEE_SIZE"]
    sync_indices = sync_committee_indices(st, st.epoch + 1, size)
    keys = [bytes(k) for k in st.small["current_sync_committee"]["pubkeys"]]
    want = [bytes(anchor_state.validators[v].pubkey) for v in sync_indices]
    if keys != want:
        raise ValueError("the anchor's sync committee is not the spec's")
    lagrange = kzg.lagrange_points()
    tau = kzg.tau_g1(lagrange)
    blobs = kzg.blob_pool(f"{cfg['name']}:blobs", mix["blobs"], tau)
    commitments = [c for _, c, _ in blobs]
    label = cfg["name"]
    share = mix["participation"]
    s0 = int(cfg["key_base"], 16)
    dom_att = st.domain(DOMAIN_BEACON_ATTESTER)
    blocks = []
    ctx = multiprocessing.get_context("spawn")
    workers = max(1, min(SIGNING_WORKERS, os.cpu_count() or 1))
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        for slot in range(1, mix["blocks"] + 1):
            process_slots(st, slot)
            if slot == 1 and st.block_root_at(0) != anchor_root:
                raise ValueError("the reference's anchor block root differs from the benchmark's")
            proposer = proposer_index(st, slot)
            parent_root = deneb.hash_tree_root(deneb.HEADER, st.small["latest_block_header"])
            atts, jobs = [], []
            for a in (slot - 1, slot - 2):
                if a < 0:
                    continue
                for c in range(committees.per_slot):
                    members = committees.committee(a, c)
                    bits = _seeded_bits(f"{label}:att:{slot}:{a}:{c}", len(members), share)
                    data = {"slot": a, "index": c, "beacon_block_root": st.block_root_at(a),
                            "source": dict(st.small["current_justified_checkpoint"]),
                            "target": {"epoch": st.epoch, "root": st.block_root_at(0)}}
                    chosen = members[np.array(bits, dtype=bool)]
                    root = spec.signing_root(
                        deneb.hash_tree_root(deneb.ATTESTATION_DATA, data), dom_att)
                    jobs.append((root, [(len(chosen) * s0 + int(chosen.astype(object).sum()))
                                        % bls.R]))
                    atts.append({"aggregation_bits": bits, "data": data})
            sync_bits = _seeded_bits(f"{label}:sync:{slot}", size, share)
            jobs.append((
                spec.signing_root(st.block_root_at(slot - 1), st.domain(DOMAIN_SYNC_COMMITTEE)),
                [sum(secret_key(cfg, sync_indices[i]) for i, b in enumerate(sync_bits) if b)
                 % bls.R],
            ))
            jobs.append((spec.signing_root(_chunk(st.epoch), st.domain(DOMAIN_RANDAO)),
                         [secret_key(cfg, proposer)]))
            sigs = [s[0] for s in sign_many(jobs, pool)]
            for att, sig in zip(atts, sigs):
                att["signature"] = sig
            body = {
                "randao_reveal": sigs[-1],
                "eth1_data": dict(st.small["eth1_data"]),
                "graffiti": b"tpu-beacon import-full-blocks".ljust(32, b"\x00"),
                "proposer_slashings": [], "attester_slashings": [],
                "attestations": atts, "deposits": [], "voluntary_exits": [],
                "sync_aggregate": {"sync_committee_bits": sync_bits,
                                   "sync_committee_signature": sigs[-2]},
                "execution_payload": _payload(st, slot, parent_root, commitments),
                "bls_to_execution_changes": [],
                "blob_kzg_commitments": list(commitments),
            }
            block = {"slot": slot, "proposer_index": proposer, "parent_root": parent_root,
                     "state_root": bytes(32), "body": body}
            pre = SigningView(slot, st.slots_per_epoch, _domains(st),
                              {s: st.block_root_at(s) for s in range(max(0, slot - 2), slot)})
            process_block(st, block, committees, sync_indices)
            field_roots = st.field_roots()
            st.last_root = block["state_root"] = spec.merkleize(field_roots)
            signed = sign_block(cfg, pre, block)
            blocks.append({
                "slot": slot, "signed": signed,
                "raw": deneb.serialize(deneb.SIGNED_BLOCK, signed),
                "root": deneb.hash_tree_root(deneb.BLOCK, block),
                "state_root": block["state_root"], "field_roots": field_roots,
                "sidecars": sidecars_of(signed, blobs),
            })
    return Chain(blocks=blocks, blobs=blobs, tau=tau, sync_indices=sync_indices,
                 anchor_root=anchor_root, anchor_state_root=anchor_state_root,
                 slots_per_epoch=st.slots_per_epoch, domains=_domains(st))


class SigningView:
    """What signing a block and checking its signatures read of its
    pre-state: the signature domains and the block roots before it."""

    def __init__(self, slot: int, slots_per_epoch: int, domains: dict, roots: dict):
        self.slot, self.slots_per_epoch = slot, slots_per_epoch
        self.domains, self.roots = domains, roots

    def domain(self, domain_type: bytes) -> bytes:
        return self.domains[domain_type]

    def block_root_at(self, slot: int) -> bytes:
        return self.roots[slot]


def _domains(st: RefState) -> dict:
    return {d: st.domain(d) for d in (DOMAIN_BEACON_ATTESTER, DOMAIN_BEACON_PROPOSER,
                                      DOMAIN_RANDAO, DOMAIN_SYNC_COMMITTEE)}


def signing_view(chain: Chain, index: int) -> SigningView:
    """The pre-state view of block `index` of the chain."""
    roots = {0: chain.anchor_root}
    roots.update({b["slot"]: b["root"] for b in chain.blocks})
    slot = chain.blocks[index]["slot"]
    return SigningView(slot, chain.slots_per_epoch, chain.domains,
                       {s: r for s, r in roots.items() if s < slot})


# --- a block with one invalid signature ------------------------------------------------


def tampered(cfg: dict, chain: Chain, index: int, swap: int) -> dict:
    """Block `index` of the chain with attestation `swap`'s signature
    replaced by the next attestation's (a valid signature over other
    data), its post-state root recomputed and re-signed by its proposer:
    raw bytes, root, state root, sidecars and the signed value."""
    entry = chain.blocks[index]
    block = pickle.loads(pickle.dumps(entry["signed"]["message"]))
    atts = block["body"]["attestations"]
    atts[swap]["signature"] = atts[(swap + 1) % len(atts)]["signature"]
    # the post-state differs only in its latest block header's body root
    header = {"slot": block["slot"], "proposer_index": block["proposer_index"],
              "parent_root": block["parent_root"], "state_root": bytes(32),
              "body_root": deneb.hash_tree_root(deneb.BODY, block["body"])}
    roots = list(entry["field_roots"])
    roots[_STATE_FIELDS.index("latest_block_header")] = deneb.hash_tree_root(deneb.HEADER, header)
    block["state_root"] = spec.merkleize(roots)
    signed = sign_block(cfg, signing_view(chain, index), block)
    return {"slot": block["slot"], "signed": signed,
            "raw": deneb.serialize(deneb.SIGNED_BLOCK, signed),
            "root": deneb.hash_tree_root(deneb.BLOCK, block), "state_root": block["state_root"],
            "sidecars": sidecars_of(signed, chain.blobs)}
