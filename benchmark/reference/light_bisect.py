"""The plain reference of the light client's skipping verification: which
headers it fetches, which it trusts, the lanes each of its checks takes
and the bytes each of them signed (whether a lane verifies is
`ed25519_ref`'s to say). Written from the published rules (reference
light/client.go:705-772 `verifySkipping`, light/verifier.go:30-88
`VerifyNonAdjacent` and :91-143 `VerifyAdjacent`, types/validation.go
`VerifyCommitLight` and `VerifyCommitLightTrusting`) and from nothing of
the program.

A header is a dict: `members` [(public key, voting power)] in the set's
order (`valset_replay.ordered`), `validators_hash` and
`next_validators_hash` as the header states them, `block` (hash, parts
total, parts hash) of the block its commit signs, and `lanes`: one entry
a member, in that order, None where the signature is absent, else
(seconds, nanos, signature) of its precommit.

The rules, from a trusted header t towards a target:

- the candidate c is first the target. If c = t + 1 (adjacent), c's
  `validators_hash` must be t's `next_validators_hash`. Otherwise (a
  jump) the TRUSTING check: c's lanes in c's order; a lane whose member
  is not in t's set adds nothing and is not taken; every other lane is
  taken and adds the power t's set gives that member, until the tally
  passes floor(t's total x num / den). Where c's lanes end first, t's set
  cannot vouch for c: the header at mid = (t + c) // 2 is fetched and
  tried first (a stack of pivots), and c is tried again once mid is
  trusted;
- then the +2/3 check of c's own set: lanes in c's order, every present
  lane taken and its power added, until the tally passes floor(c's total
  x 2 / 3); where the lanes end first, c is refused for want of power;
- c is trusted when every lane both checks took verifies (the trusting
  check's first, each check's in order: the first that does not verify
  is the one a refusal names), and becomes t.

Power decides which headers are fetched and which lanes are taken,
before any signature is looked at; lanes behind a check's stop never
are. A signature both checks of a step take is one lane."""

from __future__ import annotations

from benchmark.reference import ed25519_ref, valset_replay
from benchmark.reference.light_rule import sign_bytes


def taken(members: list, lanes: list, needed: int, trusted=None):
    """Indices of the lanes a check takes, in order, or None where the
    lanes end before the tally passes `needed`. `trusted`: {public key:
    power} of the trusted set, for the trusting check (a member outside
    it is skipped, and the power is the trusted set's)."""
    tally, out = 0, []
    for i, ((pub, power), lane) in enumerate(zip(members, lanes)):
        if lane is None:
            continue
        if trusted is not None:
            if pub not in trusted:
                continue
            power = trusted[pub]
        out.append(i)
        tally += power
        if tally > needed:
            return out
    return None


def schedule(root: int, target: int, header, trust=(1, 3)) -> dict:
    """The walk's decisions that need no signature: `pivots`, the heights
    fetched besides the target, in order; `steps`, each {"from", "height",
    "trusting" (indices, or None for an adjacent step), "own" (indices)};
    `refused`, None or (height, reason) where a step is refused before a
    lane of it is looked at. `header(h)` gives height h's dict."""
    pivots, steps, cur, stack = [], [], root, [target]
    bound = set()           # heights whose header names its own set
    while stack:
        cand = stack[-1]
        hc, ht = header(cand), header(cur)
        if cand not in bound:
            if valset_replay.validators_hash(hc["members"]) != \
                    hc["validators_hash"]:
                return {"pivots": pivots, "steps": steps,
                        "refused": (cand, "set")}
            bound.add(cand)
        trusting = None
        if cand == cur + 1:
            if hc["validators_hash"] != ht["next_validators_hash"]:
                return {"pivots": pivots, "steps": steps,
                        "refused": (cand, "binding")}
        else:
            among = dict(ht["members"])
            trusting = taken(hc["members"], hc["lanes"],
                             sum(among.values()) * trust[0] // trust[1],
                             among)
            if trusting is None:
                mid = (cur + cand) // 2
                pivots.append(mid)
                stack.append(mid)
                continue
        own = taken(hc["members"], hc["lanes"],
                    sum(p for _pub, p in hc["members"]) * 2 // 3)
        if own is None:
            return {"pivots": pivots, "steps": steps,
                    "refused": (cand, "power")}
        steps.append({"from": cur, "height": cand, "trusting": trusting,
                      "own": own})
        cur = cand
        stack.pop()
    return {"pivots": pivots, "steps": steps, "refused": None}


def step_lanes(chain_id: str, step: dict, header) -> list:
    """(check, index, (public key, sign-bytes, signature)) of every lane
    a step's checks take, the trusting check's first, in order."""
    h = header(step["height"])
    out = []
    for check, idx in ([("trusting", i) for i in step["trusting"] or ()]
                       + [("light", i) for i in step["own"]]):
        lane = h["lanes"][idx]
        out.append((check, idx, (h["members"][idx][0], sign_bytes(
            chain_id, step["height"], h["block"], lane), lane[2])))
    return out


def distinct_lanes(chain_id: str, steps: list, header) -> set:
    """The (public key, sign-bytes, signature) of every lane the steps
    take, a signature two checks take once."""
    return {lane for step in steps
            for _check, _idx, lane in step_lanes(chain_id, step, header)}


def trust(chain_id: str, steps: list, header):
    """(heights trusted, failure): the steps in order, each trusted when
    every lane of its checks verifies; failure is None, or (height,
    check, index) of the first lane that does not, where the walk
    stops."""
    trusted = []
    for step in steps:
        for check, idx, lane in step_lanes(chain_id, step, header):
            if not ed25519_ref.verify(*lane):
                return trusted, (step["height"], check, idx)
        trusted.append(step["height"])
    return trusted, None
