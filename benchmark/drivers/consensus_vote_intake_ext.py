"""Driver `consensus_vote_intake_ext`: `consensus_vote_intake` on a chain
with vote extensions on from height 1 (consensus params
`vote_extensions_enable_height` 1). The node is built as `Node` builds
it, its app as `Node.builtin_app` builds `[base] proxy_app = "kvstore"`
with `vote_extension_size` = the configuration's `vote_extension_bytes`:
the extending kvstore (`abci/kvstore.py` `ExtendingKVStoreApplication`),
which extends the node's own precommits and verifies its peers'. Every
played validator's precommit for the height's block carries its
extension (derived as the app derives it, `reference/
canonical_vote_extension.py`) and the generator's signature over it.

The traffic, the window, its timing and everything `consensus_vote_
intake.judge` holds a run to are that driver's (`window` and `judge`
call it); what differs is the node's set-up, the precommits' extensions,
and the checks below, each exact, limit 0:

- 16 seed-drawn heights of the window (the base judge's draw): the stored
  extended commit holds an extension exactly where the seen commit holds
  a precommit for the block, each the one the reference derives for that
  height and validator, its signature accepted by the reference over the
  reference's sign-bytes, which equal the program's;
- lanes: the intake's vote lanes are 2 × 149 a height and its extension
  lanes 149 (each distinct precommit's once), what the batch loop
  verified is what the intake flushed of both (the base judge's checks,
  run on the vote lanes; `ext_lanes_off`); each extension signature of a
  peer verified natively at most once (`ext_verified_twice`: the native
  ext checks, the misses on sigcache path `ext` less the lanes flushed,
  are the lanes left native and the node's own precommit a height);
- the app: it accepted exactly the extensions of the precommits the
  window's seen commits hold, its own aside, and refused none;
- a probe at the height after the base judge's probe height, before its
  proposal: one burst of four precommits, each refused and not counted,
  each with the reference's verdict beside it (a valid vote signature
  with an altered extension signature; a valid extension signature over
  altered extension bytes; a nil precommit carrying an extension; an
  extension re-signed validly that the app rejects), shuffled among up to
  32 valid extended precommits, each counted: 70 lanes at the full size,
  so the forged extensions cross the flush at the long shape where the
  program's threshold lets them (its lanes flushed are checked against
  that rule). The app is asked once for each valid one and for the last
  refusal.

`warm` is a program function (`Node.boot_kernels`, bound to the
configuration's extension size by `functools.partial`, which puts no
frame of this file on the stack: `PERF.md` §6). `PLANTS` are the
base driver's, `skip_extension_check` (a node whose `_add_vote` lets
every extension through unchecked) and `long_lanes_accepted` (a batch
verifier that accepts every lane of a longer SHA-512 shape than a vote's,
as a kernel or padding fault at the extension's shape would)."""

from __future__ import annotations

import functools
import json
import os
import random
import shutil

from benchmark.drivers import consensus_vote_intake as base
from benchmark.reference import canonical_vote_extension as cve
from benchmark.reference import ed25519_ref, vote_tally

from cometbft_tpu.node.node import Node

with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "configs", "hub-validator-150-ext.json")) as _f:
    EXTENSION_BYTES = json.load(_f)["vote_extension_bytes"]

# A tree whose node cannot warm the extension shapes has no
# `Node.boot_kernels`: the run fails here, at once, before the minutes
# of kernel tracing.
warm = functools.partial(Node.boot_kernels,
                         vote_extension_size=EXTENSION_BYTES)
PROBE_SEED = 0x5e7                # the probe's own draw beside the base's
NEIGHBOURS = 32                   # valid precommits around the probe's four
NIL = (b"", 0, b"")               # a nil block id, as `Session.vote` takes one


class Session(base.Session):
    """The base session's node, on a chain with vote extensions on."""

    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        from cometbft_tpu.config import Config, ConsensusTimeoutsConfig
        from cometbft_tpu.consensus.state import (ConsensusConfig,
                                                  ConsensusState,
                                                  intake_stats)
        from cometbft_tpu.consensus.wal import WAL
        from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
        from cometbft_tpu.db.kv import MemDB
        from cometbft_tpu.evidence.pool import EvidencePool
        from cometbft_tpu.privval.file import FilePV
        from cometbft_tpu.state.execution import BlockExecutor
        from cometbft_tpu.state.state import (ConsensusParams, GenesisDoc,
                                              State, StateStore)
        from cometbft_tpu.store.blockstore import BlockStore
        from cometbft_tpu.types.proto import Timestamp
        from cometbft_tpu.types.validator import Validator
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        self.chain_id = payload["chain_id"]
        self.n_peers = payload["node_index"]
        self.peer_ids = [f"peer{k:02d}" for k in range(config["peers"])]
        self.size = payload["vote_extension_bytes"]
        self.addresses = [cve.address(p) for p in payload["pubs"]]
        self.dir = os.path.join(base.OUT_DIR, f"{seed}-{os.getpid()}")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)

        genesis = GenesisDoc(
            chain_id=self.chain_id,
            validators=[Validator(Ed25519PubKey(p), power) for p, power
                        in zip(payload["pubs"], payload["powers"])],
            genesis_time=Timestamp(payload["genesis_seconds"], 0),
            consensus_params=ConsensusParams(
                vote_extensions_enable_height=1))
        state = State.from_genesis(genesis)
        self.members = state.validators.validators
        if [v.pub_key.bytes_() for v in self.members] != payload["pubs"]:
            raise RuntimeError("the validator set orders its members "
                               "otherwise than the generator did")
        pv = FilePV(Ed25519PrivKey(payload["node_seed"]),
                    os.path.join(self.dir, "priv_validator_state.json"))
        node_config = Config()
        node_config.base.vote_extension_size = self.size
        self.app = Node.builtin_app(node_config, pv)
        self.app.init_chain(self.chain_id, genesis.initial_height, [], b"")
        self.store = BlockStore(MemDB())
        state_store = StateStore(MemDB())
        state_store.save(state)
        pool = EvidencePool(state_store=state_store, block_store=self.store)
        executor = BlockExecutor(self.app, state_store=state_store,
                                 block_store=self.store, evidence_pool=pool)
        for row in payload["history"]:
            state = self._apply_synced(executor, state, row)
        cc = ConsensusTimeoutsConfig()
        self.cs = ConsensusState(
            ConsensusConfig(
                timeout_propose=cc.timeout_propose,
                timeout_propose_delta=cc.timeout_propose_delta,
                timeout_prevote=cc.timeout_prevote,
                timeout_prevote_delta=cc.timeout_prevote_delta,
                timeout_precommit=cc.timeout_precommit,
                timeout_precommit_delta=cc.timeout_precommit_delta,
                timeout_commit=cc.timeout_commit,
                create_empty_blocks=cc.create_empty_blocks,
                skip_timeout_commit=cc.skip_timeout_commit),
            state, executor, self.store, priv_validator=pv,
            wal=WAL(os.path.join(self.dir, "cs.wal"),
                    head_size_limit=cc.wal_head_size_limit,
                    total_size_limit=cc.wal_total_size_limit),
            name="bench-validator")
        self.cs.evidence_pool = pool
        self.committed: dict = {}
        self.cs.on_commit = self._on_commit
        self.sent = 0
        self.handled0 = intake_stats()["votes_handled"]
        self.turn = 0
        self.heights = [self._height(row) for row in payload["heights"]]
        self.next = 0
        self.cs.start()

    def wal_counts(self) -> dict:
        """The extension probe (`_extension_probe`) at the height after
        the base probe's, which has committed, while the node still runs:
        the base judge stops it here, to count its WAL, the probe's votes
        in it."""
        self.ext_probe = _extension_probe(self)
        return super().wal_counts()

    def extension(self, height: int, index: int) -> bytes:
        return cve.extension(height, self.addresses[index], self.size)

    def vote(self, row: dict, type_: int, index: int, signature=None,
             block=None, nanos=None):
        """The base session's vote; a precommit for the height's block
        carries the validator's extension and the generator's signature
        over it."""
        msg = super().vote(row, type_, index, signature, block, nanos)
        if type_ == vote_tally.PRECOMMIT and block is None:
            msg.vote.extension = self.extension(row["height"], index)
            msg.vote.extension_signature = row["precommit_ext_sigs"][index]
        return msg


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    session = Session(config, payload, boot["batch"], seed)
    for _ in range(payload["warmup_heights"]):
        if session.play(session.heights[session.next]) is None:
            raise RuntimeError("the warm-up heights fell short")
        session.next += 1
    return session


def _app_counts(session: Session) -> dict:
    return dict(session.app.extension_checks)


def _ext_cache_counts() -> dict:
    from cometbft_tpu.pipeline.cache import shared_cache
    cache = shared_cache()
    with cache._lock:
        return {"hits": cache.hits.get("ext", 0),
                "misses": cache.misses.get("ext", 0)}


def window(session: Session, seconds: float) -> dict:
    """The base driver's window; the counters gain the app's extension
    verdicts and the sigcache's path `ext`, and `hash_blocks` is the
    program's own count of the SHA-512 blocks the flushed lanes' messages
    need, vote and extension (`ops.ed25519.batch_stats()`)."""
    app0, cache0 = _app_counts(session), _ext_cache_counts()
    result = base.window(session, seconds)
    app1, cache1 = _app_counts(session), _ext_cache_counts()
    c = result["counters"]
    for key in app1:
        c[f"app_ext_{key}"] = app1[key] - app0[key]
    for key in cache1:
        c[f"sigcache_{key}_ext"] = cache1[key] - cache0[key]
    result["facts"]["hash_blocks"] = c["batch_hash_blocks_real"]
    return result


# --- the comparison that decides `correct` ----------------------------------------

def _extended_commit_diff(session: Session, row: dict) -> int:
    """Lanes of the height's stored extended commit that differ from what
    its seen commit and the reference say it holds, plus those whose
    extension signature the reference does not accept or whose sign-bytes
    are not the reference's."""
    from cometbft_tpu.types.vote import Vote
    h, p = row["height"], session.payload
    seen = session.store.load_seen_commit(h)
    ext = session.store.load_extended_commit(h)
    n = len(p["pubs"])
    if seen is None or ext is None or len(ext.signatures) != n or \
            ext.to_commit().block_id != seen.block_id:
        return n
    diff = 0
    for i, (cs, es) in enumerate(zip(seen.signatures, ext.signatures)):
        if not cs.for_block():
            diff += bool(es.extension or es.extension_signature)
            continue
        want = cve.extension(h, session.addresses[i], session.size)
        ref_bytes = cve.extension_sign_bytes(session.chain_id, h, 0,
                                             es.extension)
        mine = Vote(height=h, round=0, extension=es.extension) \
            .extension_sign_bytes(session.chain_id)
        diff += (es.extension != want or mine != ref_bytes
                 or not ed25519_ref.verify(p["pubs"][i], ref_bytes,
                                           es.extension_signature))
    return diff


def _seen_precommits(session: Session, rows: list) -> int:
    """Peer precommits for the block the window's seen commits hold."""
    seen = [session.store.load_seen_commit(r["height"]) for r in rows]
    return sum(cs.for_block() for commit in seen if commit is not None
               for cs in commit.signatures[:session.n_peers])


def _extension_probe(session: Session) -> dict:
    """Four refused precommits at the height after the base probe's,
    sent before its proposal in one burst with valid extended precommits
    around them, each against the reference's verdict."""
    from cometbft_tpu.consensus.state import intake_stats
    from cometbft_tpu.types.validation import BATCH_VERIFY_THRESHOLD
    p, cs = session.payload, session.cs
    row = session.heights[session.next + 1]["row"]
    h, n = row["height"], session.n_peers
    block = (row["block_hash"], row["parts_total"], row["parts_hash"])
    signers = [ed25519_ref.Signer(s) for s in p["signer_seeds"]]
    rng = random.Random(session.seed + PROBE_SEED)
    order = rng.sample(range(n), n)
    picked, valid = order[:4], []
    for i in order[4:4 + NEIGHBOURS]:   # short of +2/3 with the node's own
        if 3 * (sum(p["powers"][k] for k in valid + [i]) + p["powers"][n]) \
                >= 2 * sum(p["powers"]):
            break
        valid.append(i)
    altered = {}

    def precommit(index, ext=None, ext_sig=None, nil=False):
        sb = vote_tally.vote_sign_bytes(
            session.chain_id, vote_tally.PRECOMMIT, h, 0,
            None if nil else block, row["seconds"], index)
        msg = session.vote(row, vote_tally.PRECOMMIT, index,
                           signature=signers[index].sign(sb),
                           block=NIL if nil else None)
        msg.vote.extension = session.extension(h, index) if ext is None \
            else ext
        msg.vote.extension_signature = (row["precommit_ext_sigs"][index]
                                        if ext_sig is None else ext_sig)
        altered[index] = (sb, msg.vote)
        return msg

    a, b, c, d = picked
    wrong = bytes(x ^ 0x5a for x in session.extension(h, d))
    burst = [
        precommit(a, ext_sig=ed25519_ref.tamper(row["precommit_ext_sigs"][a])),
        precommit(b, ext=bytes([session.extension(h, b)[0] ^ 1])
                  + session.extension(h, b)[1:]),
        precommit(c, nil=True),
        precommit(d, ext=wrong, ext_sig=signers[d].sign(
            cve.extension_sign_bytes(session.chain_id, h, 0, wrong)))]
    burst += [session.vote(row, vote_tally.PRECOMMIT, i) for i in valid]
    rng.shuffle(burst)
    # the lanes of the burst (a nil precommit with data gets none), and
    # of them the extensions', which a flush verifies where they reach
    # the program's threshold
    lanes = 2 * (len(valid) + 3)
    app0, stats0 = _app_counts(session), intake_stats()
    ok = session.send_burst([(m, session._peer()) for m in burst])
    app1, stats1 = _app_counts(session), intake_stats()
    precommits = cs.rs.votes.precommits(0) if cs.rs.height == h else None
    out = {"ext_probe_unhandled": int(not ok or precommits is None)}
    out["ext_probe_valid_refused"] = len(valid) if precommits is None else \
        sum(precommits.get_by_index(i) is None for i in valid)
    # where the receive thread took the burst as one run (it may wake
    # before the last message is in: then the runs are shorter)
    out["ext_probe_flushed_off"] = abs(
        stats1["ext_device_lanes"] - stats0["ext_device_lanes"]
        - (lanes // 2 if lanes >= BATCH_VERIFY_THRESHOLD else 0)) \
        if stats1["runs"] - stats0["runs"] == 1 else 0
    names = ("ext_sig_altered", "ext_bytes_altered", "nil_with_ext",
             "app_rejects")
    for name, index in zip(names, picked):
        sb, vote = altered[index]
        out[f"ext_probe_{name}_counted"] = int(
            precommits is None or precommits.get_by_index(index) is not None)
        out[f"ext_probe_{name}_ref_accepts"] = int(cve.accepts(
            session.chain_id, p["pubs"][index], sb, vote.signature, h, 0,
            not vote.block_id.is_nil(), vote.extension,
            vote.extension_signature, session.size))
    out["ext_probe_app_asked_off"] = (
        abs(app1["accepted"] - app0["accepted"] - len(valid))
        + abs(app1["refused"] - app0["refused"] - 1))
    return out


def _vote_lanes_only(c: dict) -> dict:
    """The window's counters with the extension lanes taken out of the
    intake's and the batch loop's, as the base judge counts lanes."""
    out = dict(c)
    for key in ("cache_hits", "device_lanes", "native_lanes"):
        out[f"intake_{key}"] = c[f"intake_{key}"] - c[f"intake_ext_{key}"]
    out["batch_lanes"] = c["batch_lanes"] - c["intake_ext_device_lanes"]
    return out


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit), all limit 0: the
    base judge's on the vote lanes (after its probe height has committed
    it stops the node, and `Session.wal_counts` runs the extension probe
    first), then the module docstring's checks and the probe's."""
    vote_only = dict(result, counters=_vote_lanes_only(result["counters"]))
    checks = base.judge(session, vote_only, compiles)
    probe = session.ext_probe
    c = result["counters"]
    first, end = session.window
    rows = session.payload["heights"][first:end]
    played = end - first
    ext_diff = sum(_extended_commit_diff(session, r) for r in random.Random(
        session.seed).sample(rows, min(16, len(rows))))
    ext_lanes = (c["intake_ext_cache_hits"] + c["intake_ext_device_lanes"]
                 + c["intake_ext_native_lanes"])
    native_ext = c["sigcache_misses_ext"] - c["intake_ext_device_lanes"]
    return checks + [
        ("stored_extended_commit_diff", ext_diff, 0),
        ("ext_lanes_off", abs(ext_lanes - session.n_peers * played), 0),
        ("ext_verified_twice", abs(native_ext - c["intake_ext_native_lanes"]
                                   - played), 0),
        ("app_ext_accepted_off", abs(c["app_ext_accepted"]
                                     - _seen_precommits(session, rows)), 0),
        ("app_ext_refused", c["app_ext_refused"], 0),
    ] + sorted((name, value, 0) for name, value in probe.items())


# --- faults for the control runs (tools/control_runs.py) -------------------------

def _skip_extension_check():
    from cometbft_tpu.consensus.state import ConsensusState
    real = ConsensusState._check_extension
    ConsensusState._check_extension = lambda self, vote, span: None

    def undo():
        ConsensusState._check_extension = real
    return undo


def _long_lanes_accepted():
    from cometbft_tpu.crypto.keys import Ed25519BatchVerifier
    from cometbft_tpu.ops.ed25519 import VOTE_BLOCKS, hash_block_bucket
    real = Ed25519BatchVerifier.verify

    def verify(self):
        _all_ok, oks = real(self)
        oks = [ok or hash_block_bucket(len(m)) > VOTE_BLOCKS
               for ok, m in zip(oks, self._msgs)]
        return all(oks), oks
    Ed25519BatchVerifier.verify = verify

    def undo():
        Ed25519BatchVerifier.verify = real
    return undo


PLANTS = dict(base.PLANTS, skip_extension_check=_skip_extension_check,
              long_lanes_accepted=_long_lanes_accepted)
