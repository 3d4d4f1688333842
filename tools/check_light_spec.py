"""Explicit-state checker for light-client verification safety
(spec/LightClient.tla; reference spec/light-client/verification/ —
VERDICT r4 missing #6's "formal artifacts beyond one TLA+ file").

Models EXACTLY the implementation's acceptance rules
(light/verifier.py + types/validation.py):

  adjacent (h0 -> h0+1):  untrusted valset must BE the trusted
      header's next-valset (hash-bound), and its commit carries
      > floor(2/3·power) of that set;
  non-adjacent (skipping): commit signers within the TRUSTED set carry
      > floor(1/3·power(trusted)) [verify_commit_light_trusting,
      strict, floor-divided exactly as validation.py:192], and the
      commit carries > floor(2/3·power(claimed set)) of the header's
      OWN claimed valset.

Adversary model: a fixed faulty subset F signs ANYTHING (forged
headers with arbitrary claimed valsets); honest validators sign only
the canonical header of each height. The checker enumerates every
canonical chain over a valset family, every faulty subset satisfying
the fault assumption (|F ∩ C[h]| power < 1/3 of C[h] for every height
in the trust period), every reachable trusted state, and EVERY forged
header (claimed valset × signer subset) against it.

Safety (the spec's Invariant): a header accepted from a trusted state
is the canonical header of its height — forged headers are always
rejected while the fault assumption holds.

--self-test drops the fault assumption (allows F up to 2/3 of a
valset) and must FIND an accepted forgery — proving the checker can
detect unsafety, and demonstrating exactly why the 1/3 bound is the
trust assumption.

Usage: python tools/check_light_spec.py [--n 4] [--heights 4]
           [--self-test]
"""

from __future__ import annotations

import argparse
import itertools
import sys
import time


def subsets(universe, min_size=1):
    for k in range(min_size, len(universe) + 1):
        yield from itertools.combinations(universe, k)


# --- the two acceptance predicates, power-weighted ---------------------------
#
# Shared by the exhaustive model below (equal-power valsets: power =
# cardinality) and by `check_decisions`, which re-judges CONCRETE
# acceptance records (the farm's decision log, where validators carry
# real voting power). Both restate validation.py's floor-divided strict
# thresholds: needed = total * num // den, accepted iff tallied > needed.

def trusting_ok_power(signed: int, total: int,
                      num: int = 1, den: int = 3) -> bool:
    """verify_commit_light_trusting: trusted-set power that signed must
    EXCEED floor(total * num/den) (validation.py:210-216, strict)."""
    return signed > (total * num) // den


def own_commit_ok_power(signed: int, total: int) -> bool:
    """verify_commit_light: claimed-set power on the commit must EXCEED
    floor(2/3 * total) (validation.py:189-194, strict)."""
    return signed > (total * 2) // 3


def check_decisions(records):
    """Re-judge accepted-header decision records against the spec's
    acceptance rules; returns violation strings (empty = all conform).

    Each record states one farm/light acceptance as its power tallies
    (light/planner._record): `adjacent`, `valhash_bound`, `own_signed` /
    `own_total` (the header's own claimed set on its commit), and for
    skipping steps `trusted_signed` / `trusted_total` (trusted-set
    power that signed) plus the trust fraction. This is the bridge the
    light-farm simnet scenario crosses: every header the farm accepted
    must satisfy exactly the rules the exhaustive model proves safe."""
    errs = []
    for i, r in enumerate(records):
        label = (f"record {i} h={r.get('height')} "
                 f"session={r.get('session', '?')}")
        if not own_commit_ok_power(r["own_signed"], r["own_total"]):
            errs.append(
                f"{label}: own-commit power {r['own_signed']}/"
                f"{r['own_total']} fails the >2/3 rule")
        if r.get("adjacent"):
            if not r.get("valhash_bound"):
                errs.append(f"{label}: adjacent step accepted without "
                            f"valset-hash binding")
        elif not trusting_ok_power(r["trusted_signed"],
                                   r["trusted_total"],
                                   r.get("trust_num", 1),
                                   r.get("trust_den", 3)):
            errs.append(
                f"{label}: trusting power {r['trusted_signed']}/"
                f"{r['trusted_total']} fails the "
                f">{r.get('trust_num', 1)}/{r.get('trust_den', 3)} "
                f"rule")
    return errs


class LightModel:
    def __init__(self, n=4, heights=4, min_valset=3,
                 break_assumption=False):
        self.n = n
        self.vals = tuple(range(n))
        self.heights = heights
        # candidate valsets for canonical chains (equal power 1 each)
        self.valsets = [frozenset(s) for s in
                        subsets(self.vals, min_valset)]
        self.break_assumption = break_assumption

    # --- the implementation's two threshold rules ------------------------

    @staticmethod
    def trusting_ok(signers, trusted) -> bool:
        """validation.py:192-194 + tallied > needed (strict); equal
        power, so power = cardinality."""
        return trusting_ok_power(len(signers & trusted), len(trusted))

    @staticmethod
    def own_commit_ok(signers, claimed) -> bool:
        """verify_commit_light: signers must be members; > 2/3."""
        return (signers <= claimed
                and own_commit_ok_power(len(signers), len(claimed)))

    # --- enumeration ------------------------------------------------------

    def fault_sets(self, chain):
        """Faulty subsets F consistent with the fault assumption over
        the whole chain (or ALL subsets when --self-test breaks it)."""
        for f in subsets(self.vals, 1):
            F = frozenset(f)
            if self.break_assumption:
                yield F
            elif all(len(F & c) <= (len(c) - 1) // 3 for c in chain):
                # strictly below 1/3 of every canonical valset
                yield F

    def check_chain(self, chain, F):
        """BFS over trusted states (height index into the chain);
        returns a violation string or None. Trusted state h means the
        client trusts canonical header h with valset chain[h]."""
        # every forged header: claimed valset W + signers S ⊆ F ∪ ∅
        # (honest validators never sign a forged header)
        reachable = {0}
        frontier = [0]
        while frontier:
            h0 = frontier.pop()
            trusted = chain[h0]
            has_skip_target = h0 + 2 < len(chain)
            # skipping-forgery acceptance depends only on the trusted
            # state, not the target height — check ONCE per h0
            if has_skip_target:
                for s in subsets(F):
                    S = frozenset(s)
                    if not self.trusting_ok(S, trusted):
                        continue
                    for w in subsets(self.vals):
                        W = frozenset(w)
                        if self.own_commit_ok(S, W):
                            return (f"SKIPPING FORGERY accepted: "
                                    f"trusted h{h0} {set(trusted)}, "
                                    f"faulty {set(F)} claimed "
                                    f"{set(W)} signers {set(S)}")
            for h in range(h0 + 1, len(chain)):
                adjacent = h == h0 + 1
                # 1) canonical header of height h: honest+faulty of
                # chain[h] may all sign — the client should accept
                canon_signers = chain[h]
                if adjacent:
                    ok = self.own_commit_ok(canon_signers, chain[h])
                else:
                    ok = (self.trusting_ok(canon_signers, trusted)
                          and self.own_commit_ok(canon_signers,
                                                 chain[h]))
                if ok and h not in reachable:
                    reachable.add(h)
                    frontier.append(h)
                # 2) forged ADJACENT header: hash-bound — claimed
                # valset must be the real next valset chain[h]; only
                # the content forks
                if adjacent:
                    for s in subsets(F):
                        S = frozenset(s)
                        if self.own_commit_ok(S, chain[h]):
                            return (f"ADJACENT FORGERY accepted: "
                                    f"trusted h{h0} {set(trusted)}, "
                                    f"faulty {set(F)} forged h{h} "
                                    f"signers {set(S)}")
        return None

    def run(self):
        """All chains × all fault sets; returns (n_configs,
        violation-or-None)."""
        n_cfg = 0
        for chain in itertools.product(self.valsets,
                                       repeat=self.heights):
            for F in self.fault_sets(chain):
                n_cfg += 1
                err = self.check_chain(chain, F)
                if err:
                    return n_cfg, err
        return n_cfg, None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--heights", type=int, default=4)
    ap.add_argument("--min-valset", type=int, default=3)
    ap.add_argument("--self-test", action="store_true",
                    help="drop the <1/3 fault assumption; an accepted "
                         "forgery MUST be found")
    args = ap.parse_args(argv)

    model = LightModel(args.n, args.heights, args.min_valset,
                       break_assumption=args.self_test)
    t0 = time.monotonic()
    n_cfg, err = model.run()
    dt = time.monotonic() - t0
    scope = (f"n={args.n} heights={args.heights} "
             f"valsets>={args.min_valset}")

    if args.self_test:
        if err:
            print(f"SELF-TEST OK: without the fault assumption the "
                  f"checker finds: {err}  [{n_cfg:,} configs, "
                  f"{dt:.1f}s]")
            return 0
        print("SELF-TEST FAILED: no forgery found even without the "
              "fault assumption — checker cannot detect unsafety")
        return 1
    if err:
        print(f"VIOLATION ({scope}): {err}  [{n_cfg:,} configs]")
        return 1
    print(f"OK ({scope}): no forged header accepted across {n_cfg:,} "
          f"(chain × faulty-set) configs, all trusted states, all "
          f"forged headers ({dt:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
