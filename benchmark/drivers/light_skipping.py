"""Driver `light_skipping`: the window is a closed loop of updates of ONE
skipping light client kept across the window, each update
`LightClient.verify_light_block_at_height(1 + update_headers x i)` from
the height the update before trusted, timed from the call to its
return; the client's `now` is the target's time + 60 s, so the header it
trusts is `update_headers` minutes old when it is used, as in upstream's
`BenchmarkBisection`.

The client is built as `cmd/main.py` `cmd_light` builds it (skipping,
the constructor's default): a primary provider and one witness, a second
provider object over the primary's chain (upstream passes the full node
twice), a `LightStore` over `MemDB`, the process-wide `shared_cache()`.
The providers hand out, a height, a `LightBlock` built before the window
with a `ValidatorSet` object of its own; a height the plain reference's
schedule does not fetch was never made, and asking for it raises, which
fails the update. Wire decoding is left out.

`commit_verify_p50_ms` / `_p95_ms` are the latency of one update over
all updates of the window. What `judge` holds a run to, every limit 0,
is in its docstring; then three probes through the same entry on chains
of their own. `PLANTS` are the hub driver's (`Ed25519BatchVerifier
.verify` taking every lane, or the upper half of a flush, for good):
under both a probe's altered lane is trusted. `warm` is `node_boot.boot`
itself."""

from __future__ import annotations

import random
import time

from jax.profiler import TraceAnnotation

from benchmark.drivers import node_boot, verify_commit_loop
from benchmark.harness import stats
from benchmark.reference import ed25519_ref, light_bisect, light_rule

# A tree from before the planned skipping walk has neither this counter
# nor the walk it counts: there this import fails, and with it the run,
# at once and before the minutes of kernel tracing.
from cometbft_tpu.light.client import (LightClient, TrustOptions,
                                       bisect_stats)
from cometbft_tpu.ops.ed25519 import batch_stats

SIGCACHE_PATH = "light"
PLANTS = verify_commit_loop.PLANTS
warm = node_boot.boot


class ChainProvider:
    """Light blocks of the heights the generator made, each built once,
    before the window, with its own `ValidatorSet`; `fetched` logs the
    heights asked for."""

    def __init__(self, chain: dict, blocks: dict = None):
        self._chain_id = chain["chain_id"]
        self._blocks = blocks if blocks is not None else _light_blocks(chain)
        self.fetched = []

    def chain_id(self) -> str:
        return self._chain_id

    def light_block(self, height: int):
        from cometbft_tpu.light.provider import ErrLightBlockNotFound
        with TraceAnnotation("bench.fetch"):
            self.fetched.append(height)
            if height not in self._blocks:
                raise ErrLightBlockNotFound(
                    f"height {height} is not in the reference's schedule")
            return self._blocks[height]


def _light_blocks(chain: dict) -> dict:
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.light.types import LightBlock, SignedHeader
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    return {h: LightBlock(
        SignedHeader(chain["headers"][h], chain["commits"][h]),
        ValidatorSet([Validator(Ed25519PubKey(pub), power)
                      for pub, power in chain["members"][h]]))
        for h in chain["headers"]}


def client_of(config: dict, chain: dict) -> dict:
    """A skipping client at its trust root (height 1), and what the
    judge reads of it."""
    from cometbft_tpu.db.kv import MemDB
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.types.proto import Timestamp
    primary = ChainProvider(chain)
    witness = ChainProvider(chain, primary._blocks)
    store = LightStore(MemDB())
    root_time = chain["headers"][1].time.seconds
    client = LightClient(
        chain["chain_id"],
        TrustOptions(config["trusting_period_s"], 1, chain["hashes"][1]),
        primary, [witness], store,
        trust_level=_fraction(config),
        now_fn=lambda: Timestamp(root_time + 60, 0))
    primary.fetched.clear()
    return {"client": client, "store": store, "chain": chain,
            "primary": primary}


def _fraction(config: dict):
    from cometbft_tpu.types.validation import Fraction
    return Fraction(*config["trust_level"])


class Session:
    def __init__(self, config: dict, payload: dict, batch: int, seed: int):
        self.config, self.payload = config, payload
        self.batch, self.seed = batch, seed
        self.main = client_of(config, payload["main"])


def build(config: dict, traffic: dict, payload: dict, boot: dict,
          seed: int) -> Session:
    """The window's client (its trust root verified, as every client's
    is when it is made), and one update of a throwaway chain through the
    same entry, so that lazy imports and the device path's first
    transfers are paid in set-up."""
    session = Session(config, payload, boot["batch"], seed)
    warm_chain = payload["warmup"]
    node = client_of(config, warm_chain)
    for i in range(1, warm_chain["updates"] + 1):
        _update(node, i)
    return session


def _target(chain: dict, i: int) -> int:
    return 1 + chain["span"] * i


def _update(node: dict, i: int) -> None:
    """Update i (from 1) of a client: to its target, `now` 60 s after
    the target's time."""
    from cometbft_tpu.types.proto import Timestamp
    chain = node["chain"]
    height = _target(chain, i)
    now = Timestamp(chain["headers"][height].time.seconds + 60, 0)
    with TraceAnnotation("bench.update"):
        node["client"].verify_light_block_at_height(height, now)


def _snapshot() -> dict:
    return {"device": node_boot.device_counters(), "bisect": bisect_stats(),
            "batch": batch_stats()}


def _delta(before: dict, after: dict) -> dict:
    counters = node_boot.delta(before["device"], after["device"],
                               SIGCACHE_PATH)
    for key, now in after["bisect"].items():
        counters[f"bisect_{key}"] = now - before["bisect"][key]
    for key, now in after["batch"].items():
        counters[f"batch_{key}"] = now - before["batch"][key]
    return counters


def _ref_header(chain: dict):
    """Height h of a chain as the plain reference reads it."""
    def header(h: int) -> dict:
        hdr, commit = chain["headers"][h], chain["commits"][h]
        bid = commit.block_id
        return {"members": chain["members"][h],
                "validators_hash": hdr.validators_hash,
                "next_validators_hash": hdr.next_validators_hash,
                "block": (bid.hash, bid.parts.total, bid.parts.hash),
                "lanes": [None if cs.absent_() else
                          (cs.timestamp.seconds, cs.timestamp.nanos,
                           cs.signature) for cs in commit.signatures]}
    return header


def _distinct(chain: dict, updates: int) -> list:
    """Distinct lanes the reference's steps take, an update: the lanes
    both checks of a step take are its commit's indices both took, and
    no two steps share a height."""
    return [sum(len(set(step["trusting"] or ()) | set(step["own"]))
                for step in sched["steps"])
            for sched in chain["schedules"][:updates]]


def window(session: Session, seconds: float) -> dict:
    node, chain = session.main, session.main["chain"]
    latencies, failed = [], 0
    before = _snapshot()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    for i in range(1, chain["updates"] + 1):
        t = time.perf_counter()
        if t >= deadline:
            break
        try:
            _update(node, i)
        except Exception as exc:  # noqa: BLE001 — counted, judged
            failed += 1
            print(f"[window] update {i} raised {exc!r}", flush=True)
        latencies.append(time.perf_counter() - t)
    elapsed = time.perf_counter() - t0
    done = len(latencies)
    counters = _delta(before, _snapshot())
    if done == chain["updates"]:
        print("[window] the updates ran out before the window's end: a "
              "later benchmark PR resizes the traffic", flush=True)
    counters["stream_exhausted"] = int(done == chain["updates"])
    distinct = _distinct(chain, done)
    counters["implied_chunks"] = node_boot.implied_chunks(distinct,
                                                          session.batch)
    last = _target(chain, chain["updates"])
    msg_len = len(light_rule.sign_bytes(
        chain["chain_id"], last, _ref_header(chain)(last)["block"],
        _ref_header(chain)(last)["lanes"][0]))
    ms = [s * 1e3 for s in latencies]
    return {
        "end_to_end": {"commit_verify_p50_ms": stats.percentile(ms, 50),
                       "commit_verify_p95_ms": stats.percentile(ms, 95)},
        "attempted": done, "failed": failed, "counters": counters,
        "facts": {"window_s": elapsed, "lanes": sum(distinct),
                  "hash_blocks": sum(distinct)
                  * node_boot.hash_blocks(msg_len),
                  "calls": done, "updates_per_s": stats.rate(done, elapsed),
                  "steps": counters["bisect_steps"]},
    }


def judge(session: Session, result: dict, compiles: int) -> list:
    """Every number compared, as (name, value, limit): all are exact
    comparisons, so every limit is 0. Of the window: no update failed;
    the store's latest is the last target done, each target done and 64
    seed-drawn heights the plain reference trusts are in the store, the
    program's hash of each equal to the benchmark's own
    (`reference/header_hash.py`, the hash its commit signs), and the steps trusted are the
    reference's count; the primary fetched exactly each target and then the
    reference's pivots, in order; the distinct lanes verified (flushed
    and native) are the reference's distinct lanes, not one fewer and not
    one more, with no cache hit among them; on a device each update is
    one flush, none native, and what the batch loop saw is what the
    client flushed; 512 seed-drawn lanes of the reference's steps
    accepted by the reference over its own sign-bytes, equal to the
    program's; dispatches = the chunks the reference's lanes imply; the
    zeros."""
    node, chain = session.main, session.main["chain"]
    c, done = result["counters"], result["attempted"]
    store, rng = node["store"], random.Random(session.seed)
    header = _ref_header(chain)
    trusted = [1] + [step["height"] for sched in chain["schedules"][:done]
                     for step in sched["steps"]]
    fetched = [h for i, sched in enumerate(chain["schedules"][:done])
               for h in [_target(chain, i + 1)] + sched["pivots"]]
    drawn = [_target(chain, i) for i in range(1, done + 1)] + \
        rng.sample(trusted, min(64, len(trusted)))
    hash_diff = 0
    for h in drawn:
        lb = store.light_block(h)
        hash_diff += lb is None or lb.header.hash() != chain["hashes"][h]
    ref_rejects = signbytes_diff = 0
    for _ in range(512 if done else 0):
        sched = chain["schedules"][rng.randrange(done)]
        step = rng.choice(sched["steps"])
        _check, idx, lane = rng.choice(light_bisect.step_lanes(
            chain["chain_id"], step, header))
        ref_rejects += not ed25519_ref.verify(*lane)
        signbytes_diff += lane[1] != chain["commits"][
            step["height"]].vote_sign_bytes(chain["chain_id"], idx)
    distinct = sum(_distinct(chain, done))
    verified = c["bisect_device_lanes"] + c["bisect_native_lanes"]
    on_device = session.batch > 0
    checks = [
        ("updates_failed", result["failed"], 0),
        ("latest_gap", abs(store.latest().height - trusted[-1]), 0),
        ("trusted_hash_diff", hash_diff, 0),
        ("fetched_diff", int(node["primary"].fetched != fetched), 0),
        ("lanes_gap", abs(distinct - verified), 0),
        ("lanes_distinct_gap", abs(distinct - c["bisect_lanes_distinct"]),
         0),
        ("steps_gap", abs(len(trusted) - 1 - c["bisect_steps"]), 0),
        ("flushes_gap", abs(done - c["bisect_flushes"]) if on_device else 0,
         0),
        ("native_lanes", c["bisect_native_lanes"] if on_device else 0, 0),
        ("batch_lanes_gap", abs(c["batch_lanes"] - c["bisect_device_lanes"])
         if on_device else 0, 0),
        ("ref_rejects", ref_rejects, 0),
        ("signbytes_diff", signbytes_diff, 0),
        ("window_compiles", compiles, 0),
        ("sigcache_hits", c["sigcache_hits"] + c["bisect_cache_hits"], 0),
        ("pallas_degraded", c["pallas_degraded"], 0),
        ("canary_trips", c["canary_trips"], 0),
        ("attributed_chunks", c["batch_attributed_chunks"], 0),
        ("dispatch_gap", abs(c["dispatches"] - c["implied_chunks"]), 0),
    ]
    return checks + _probes(session)


def _probes(session: Session) -> list:
    """Through the same entry, after the window, each a chain of one
    update whose one altered lane the generator chose from the
    reference's schedule. `trusting_lane`: the update is refused at step
    `probe_trusting_step` with ErrInvalidHeader "trusting verify failed"
    over ErrWrongSignature at that lane, the steps before it trusted, and
    the reference rejects the lane; `own_lane`: refused at the target
    with "invalid commit" over that lane, every step before it trusted;
    `behind_stops`: accepted, as the reference takes the lane in neither
    check. In each, the heights fetched are the reference's."""
    from cometbft_tpu.light.verifier import ErrInvalidHeader
    from cometbft_tpu.types.validation import ErrWrongSignature
    payload = session.payload
    out = []
    for name, chain in payload["probes"].items():
        height, lane = payload["probe_alters"][name]
        node = client_of(session.config, chain)
        raised = None
        try:
            _update(node, 1)
        except Exception as exc:    # judged by how far it got, and by name
            raised = exc
        sched = chain["schedules"][0]
        steps = [step["height"] for step in sched["steps"]]
        trusted, failure = light_bisect.trust(
            chain["chain_id"], sched["steps"], _ref_header(chain))
        stored = [h for h in chain["headers"]
                  if node["store"].light_block(h) is not None]
        cause = getattr(raised, "__cause__", None)
        out.append((f"probe_{name}_fetched_diff", int(
            node["primary"].fetched != [_target(chain, 1)]
            + sched["pivots"]), 0))
        if name == "behind_stops":
            out += [
                (f"probe_{name}_refused", int(raised is not None), 0),
                (f"probe_{name}_stored_diff", int(stored != [1] + steps), 0),
                (f"probe_{name}_ref_refuses", int(failure is not None), 0),
            ]
            continue
        check, prefix, at = (("trusting", "trusting verify failed",
                              payload["probe_trusting_step"] - 1)
                             if name == "trusting_lane" else
                             ("light", "invalid commit", len(steps) - 1))
        out += [
            (f"probe_{name}_not_refused", int(not (
                isinstance(raised, ErrInvalidHeader)
                and isinstance(cause, ErrWrongSignature)
                and str(raised).startswith(prefix))), 0),
            (f"probe_{name}_wrong_lane",
             int(getattr(cause, "idx", None) != lane), 0),
            (f"probe_{name}_stored_diff",
             int(stored != [1] + steps[:at]), 0),
            (f"probe_{name}_ref_differs", int(
                failure != (height, check, lane) or trusted != steps[:at]),
             0),
        ]
    return out
