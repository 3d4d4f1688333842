"""`ext_check_ms_per_height.*`: the program's `consensus.ext_check`
spans (consensus/state.py `_add_vote` → `_check_extension`: a peer
precommit's extension signature looked up in the sigcache, verified
natively on a miss, then the app's `VerifyVoteExtension`; attributes
`height`, `cache_hit`, `app_ok`) summed by `height`, median over the
heights, in ms, host clock. The `[layer]` line gives the spans' cache
hits and the native checks of extension signatures (`vote.verify` spans
with `path` "ext", the node's own precommit's among them). Nothing to
read where the program opens no such span."""

from benchmark.harness import stats


def read(ctx):
    by_height: dict = {}
    hits = checks = 0
    for s in ctx.spans:
        if s["name"] != "consensus.ext_check" or s["t1"] < s["t0"]:
            continue
        attrs = s.get("attrs", {})
        h = attrs.get("height")
        by_height[h] = by_height.get(h, 0.0) + (s["t1"] - s["t0"]) / 1e6
        checks += 1
        hits += attrs.get("cache_hit", 0)
    if not by_height:
        return None
    native = sum(1 for s in ctx.spans if s["name"] == "vote.verify"
                 and s.get("attrs", {}).get("path") == "ext")
    print(f"[layer] consensus.ext_check: {checks} spans over "
          f"{len(by_height)} heights, {hits} cache hits; {native} native "
          f"checks of extension signatures (vote.verify, path ext)",
          flush=True)
    return stats.median(list(by_height.values()))
