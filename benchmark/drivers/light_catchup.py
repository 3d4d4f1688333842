"""Driver `light_catchup`: the window is ONE
`LightClient.verify_light_block_at_height(tip)` of a sequential light
client whose trust root is height 1 of a chain the process has never
seen, timed from the call to its return.

The client is built as `cmd/main.py` `cmd_light` builds it, with the
constructor's `sequential=True` (what `cometbft_tpu light --sequential`
hands it): a primary provider, no witnesses, a `LightStore` over `MemDB`,
the process-wide `shared_cache()`. The provider holds the chain in
memory and hands out, a height, a `LightBlock` built before the window
with a `ValidatorSet` object of its own (no set hash, address or JSON
memo is shared between two headers, as none is between two responses of
an RPC provider); wire decoding is left out.

`catchup_sigs_per_s` counts the lanes the +2/3 rule TAKES of every
header the one call trusted (42 of a commit's 150 here, by the plain
reference `light_rule`), over the whole time of the call; headers/s is
on the `[window]` line beside it.

What `judge` holds a run to, every limit 0, is in its docstring; it does
NOT pin where the client cuts its tiles. Then three probes through the
same entry on chains of their own. `PLANTS` are the hub driver's
(`Ed25519BatchVerifier.verify` taking every lane, or the upper half of a
flush, for good): under both the probe's altered lane is trusted.
`warm` is `node_boot.boot` itself (`PERF.md` section 6, PR 29: a frame
of this file on the stack under which the kernels are traced costs 40 s)."""

from __future__ import annotations

import random
import time

from jax.profiler import TraceAnnotation

from benchmark.drivers import node_boot, verify_commit_loop
from benchmark.harness import stats
from benchmark.reference import ed25519_ref, light_rule

# A tree from before PR 36 has neither this counter nor the tiled walk it
# counts: there this import fails, and with it the run, at once and
# before the minutes of kernel tracing.
from cometbft_tpu.light.client import (LightClient, TrustOptions,
                                       tile_stats)
from cometbft_tpu.ops.ed25519 import batch_stats

SIGCACHE_PATH = "light"
PLANTS = verify_commit_loop.PLANTS
warm = node_boot.boot


class ChainProvider:
    """The primary: light blocks 1..n of one chain, each built once,
    before the window, with its own `ValidatorSet`."""

    def __init__(self, chain: dict):
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.light.types import LightBlock, SignedHeader
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        self._chain_id = chain["chain_id"]
        self._blocks = [
            LightBlock(SignedHeader(header, commit), ValidatorSet([
                Validator(Ed25519PubKey(pub), power)
                for pub, power in chain["members"]]))
            for header, commit in zip(chain["headers"], chain["commits"])]

    def chain_id(self) -> str:
        return self._chain_id

    def light_block(self, height: int):
        from cometbft_tpu.light.provider import ErrLightBlockNotFound
        with TraceAnnotation("bench.fetch"):
            if height == 0:
                height = len(self._blocks)
            if not 1 <= height <= len(self._blocks):
                raise ErrLightBlockNotFound(f"no light block at {height}")
            return self._blocks[height - 1]


def client_of(config: dict, chain: dict) -> dict:
    """A light client at its trust root, and what the judge reads of it."""
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.types.proto import Timestamp
    store = LightStore(MemDB())
    now = Timestamp(chain["now_seconds"], 0)
    root = config["trust_root_height"]
    client = LightClient(
        chain["chain_id"],
        TrustOptions(config["trusting_period_s"], root,
                     chain["hashes"][root - 1]),
        ChainProvider(chain), [], store, sequential=True,
        now_fn=lambda: now)
    return {"client": client, "store": store, "chain": chain, "now": now}


class Session:
    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        self.main = client_of(config, payload["main"])


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    """Build the window's client (its trust root verified, as every
    client's is when it is made), and take a throwaway chain through the
    same entry once, so that lazy imports and the device path's first
    transfers are paid in set-up."""
    session = Session(config, payload, boot["batch"], seed)
    warm_chain = payload["warmup"]
    node = client_of(config, warm_chain)
    node["client"].verify_light_block_at_height(warm_chain["n_headers"])
    if node["store"].latest().height != warm_chain["n_headers"]:
        raise RuntimeError("the warm-up catch-up fell short")
    return session


def _rule_lanes(chain: dict, height: int):
    """Indices of the lanes the plain rule takes of one commit, or None
    where it refuses the commit for want of power."""
    return light_rule.taken(chain["members"], [
        None if cs.absent_() else
        (cs.timestamp.seconds, cs.timestamp.nanos, cs.signature)
        for cs in chain["commits"][height - 1].signatures])


def _taken_counts(chain: dict, lo: int, hi: int) -> list:
    """Lanes the rule takes of headers lo..hi, a header."""
    return [len(_rule_lanes(chain, h) or ()) for h in range(lo, hi + 1)]


def _snapshot() -> dict:
    return {"device": node_boot.device_counters(), "tiles": tile_stats(),
            "batch": batch_stats()}


def _delta(before: dict, after: dict) -> dict:
    counters = node_boot.delta(before["device"], after["device"],
                               SIGCACHE_PATH)
    for key, now in after["tiles"].items():
        counters[f"light_{key}"] = now - before["tiles"][key]
    for key, now in after["batch"].items():
        counters[f"batch_{key}"] = now - before["batch"][key]
    # the program's own count of the bucket-wide chunks its flushes were
    # cut into: where the tiles are cut is the program's to choose
    counters["implied_chunks"] = counters["batch_chunks"]
    return counters


def _catch_up(node: dict):
    """The timed entry: (seconds, exception or None, counters)."""
    before = _snapshot()
    tip = node["chain"]["n_headers"]
    raised = None
    t0 = time.perf_counter()
    with TraceAnnotation("bench.catchup"):
        try:
            node["client"].verify_light_block_at_height(tip, node["now"])
        except Exception as exc:    # judged by how far it got, and by name
            raised = exc
    elapsed = time.perf_counter() - t0
    return elapsed, raised, _delta(before, _snapshot())


def window(session: Session, seconds: float) -> dict:
    node = session.main
    chain = node["chain"]
    root = session.config["trust_root_height"]
    elapsed, raised, counters = _catch_up(node)
    if raised is not None:
        print(f"[window] the catch-up gave up: {raised!r}", flush=True)
    latest = node["store"].latest().height
    taken = _taken_counts(chain, root + 1, latest)
    msg_len = len(_reference_lane(chain, chain["n_headers"], 0)[1])
    return {
        "end_to_end": {"catchup_sigs_per_s": stats.rate(sum(taken),
                                                        elapsed)},
        "attempted": chain["n_headers"] - root,
        "failed": chain["n_headers"] - latest,
        "counters": counters,
        "facts": {"window_s": elapsed, "lanes": sum(taken),
                  "hash_blocks": sum(taken) * node_boot.hash_blocks(msg_len),
                  "headers": latest - root,
                  "headers_per_s": stats.rate(latest - root, elapsed),
                  "tiles": counters["light_tiles"], "calls": 1},
    }


def _reference_lane(chain: dict, height: int, idx: int):
    """(pub, message, signature) of one lane, the message from the
    benchmark's own encoder."""
    commit = chain["commits"][height - 1]
    cs, bid = commit.signatures[idx], commit.block_id
    lane = (cs.timestamp.seconds, cs.timestamp.nanos, cs.signature)
    return chain["members"][idx][0], light_rule.sign_bytes(
        chain["chain_id"], height, (bid.hash, bid.parts.total,
                                    bid.parts.hash), lane), cs.signature


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit): all are exact
    comparisons, so every limit is 0. Of the window: the store's latest
    is the tip; 33 seed-drawn heights hold the generator's header hash
    and the reference's `validators_hash`; the lanes verified (flushed
    and native) are the lanes the plain rule takes, not one fewer and
    not one more, with no cache hit among them; on a device only the
    chain's last tile may have verified natively, and what the batch
    loop saw is what the client flushed (`native_over`: natively verified
    lanes beyond one flush under `BATCH_VERIFY_THRESHOLD`); 512
    seed-drawn taken lanes accepted by the reference over its own
    sign-bytes, equal to the program's; dispatches = the program's chunk
    count; the zeros."""
    node, chain = session.main, session.main["chain"]
    n, root = chain["n_headers"], session.config["trust_root_height"]
    store, rng, c = node["store"], random.Random(session.seed), \
        result["counters"]
    want_hash = light_rule.validators_hash(chain["members"])
    heights = sorted(set(rng.sample(range(1, n + 1), min(32, n)) + [n]))
    stored = {h: store.light_block(h) for h in heights}
    taken = _taken_counts(chain, root + 1, n)
    ref_rejects = signbytes_diff = 0
    for _ in range(512):
        h = rng.randrange(root + 1, n + 1)
        idx = rng.choice(_rule_lanes(chain, h))
        pub, msg, sig = _reference_lane(chain, h, idx)
        ref_rejects += not ed25519_ref.verify(pub, msg, sig)
        signbytes_diff += msg != chain["commits"][h - 1].vote_sign_bytes(
            chain["chain_id"], idx)
    verified = c["light_device_lanes"] + c["light_native_lanes"]
    on_device = session.batch > 0
    # on a device only a flush under the seam's threshold verifies
    # natively, and only the chain's last tile can be that short
    from cometbft_tpu.types.validation import BATCH_VERIFY_THRESHOLD
    checks = [
        ("latest_short", n - store.latest().height, 0),
        ("header_hash_diff", sum(
            1 for h, lb in stored.items()
            if lb is None or lb.header.hash() != chain["hashes"][h - 1]), 0),
        ("validators_hash_diff", sum(
            1 for lb in stored.values()
            if lb is None or lb.header.validators_hash != want_hash
            or lb.validator_set.hash() != want_hash), 0),
        ("lanes_gap", abs(sum(taken) - verified), 0),
        ("planned_gap", abs(c["light_lanes"] - verified), 0),
        ("headers_gap", abs((n - root) - c["light_headers"]), 0),
        ("native_over", max(0, c["light_native_lanes"]
                            - (BATCH_VERIFY_THRESHOLD - 1))
         if on_device else 0, 0),
        ("batch_lanes_gap", abs(c["batch_lanes"] - c["light_device_lanes"])
         if on_device else 0, 0),
        ("ref_rejects", ref_rejects, 0),
        ("signbytes_diff", signbytes_diff, 0),
        ("window_compiles", compiles, 0),
        ("sigcache_hits", c["sigcache_hits"] + c["light_cache_hits"], 0),
        ("pallas_degraded", c["pallas_degraded"], 0),
        ("canary_trips", c["canary_trips"], 0),
        ("attributed_chunks", c["batch_attributed_chunks"], 0),
        ("dispatch_gap", abs(c["dispatches"] - c["implied_chunks"]), 0),
    ]
    return checks + _probes(session)


def _probes(session: Session) -> list:
    """Through the same entry, after the window, each on a chain of its
    own whose header `bad` is at fault. `altered`: a lane the rule takes
    does not verify: the call raises ErrInvalidHeader over
    ErrWrongSignature at that index, the store's latest is bad - 1, the
    reference rejects that lane. `beyond`: the same in a lane behind the
    rule's stop: accepted to the tip, as the reference's rule never takes
    it. `short`: the heaviest lanes absent: refused for want of power
    with not one lane of that header verified, latest bad - 1."""
    from cometbft_tpu.light.verifier import ErrInvalidHeader
    from cometbft_tpu.types.validation import (
        ErrNotEnoughVotingPowerSigned, ErrWrongSignature)
    payload, root = session.payload, session.config["trust_root_height"]
    bad = payload["probe_bad_height"]
    out = []
    for name, chain in payload["probes"].items():
        node = client_of(session.config, chain)
        _elapsed, raised, c = _catch_up(node)
        latest = node["store"].latest().height
        rule = _rule_lanes(chain, bad)
        verified = c["light_device_lanes"] + c["light_native_lanes"]
        cause = getattr(raised, "__cause__", None)
        if name == "altered":
            lane = payload["probe_bad_lane"]
            out += [
                ("probe_altered_not_refused", int(not (
                    isinstance(raised, ErrInvalidHeader)
                    and isinstance(cause, ErrWrongSignature))), 0),
                ("probe_altered_wrong_lane",
                 int(getattr(cause, "idx", None) != lane), 0),
                ("probe_altered_latest_gap", abs(latest - (bad - 1)), 0),
                ("probe_altered_ref_accepts", int(
                    lane not in rule or ed25519_ref.verify(
                        *_reference_lane(chain, bad, lane))), 0),
            ]
        elif name == "beyond":
            lane = payload["probe_beyond_lane"]
            out += [
                ("probe_beyond_refused", int(raised is not None), 0),
                ("probe_beyond_latest_gap",
                 abs(latest - chain["n_headers"]), 0),
                ("probe_beyond_ref_takes", int(lane in rule), 0),
                ("probe_beyond_ref_accepts", int(ed25519_ref.verify(
                    *_reference_lane(chain, bad, lane))), 0),
            ]
        else:
            out += [
                ("probe_short_not_refused", int(not (
                    isinstance(raised, ErrInvalidHeader) and isinstance(
                        cause, ErrNotEnoughVotingPowerSigned))), 0),
                ("probe_short_latest_gap", abs(latest - (bad - 1)), 0),
                ("probe_short_ref_passes", int(rule is not None), 0),
                # before its lanes are flushed: what was verified is the
                # headers before it and nothing of it
                ("probe_short_lanes_gap", abs(verified - sum(
                    _taken_counts(chain, root + 1, bad - 1))), 0),
            ]
    return out
