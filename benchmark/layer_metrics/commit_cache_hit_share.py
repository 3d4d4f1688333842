"""`commit_cache_hit_share.*`: of the lanes `verify_commit` looked up in
the verified-signature cache over the window (`SigCache` hits and misses
on path `commit`), the share it found verified, in %. 100 for a
validator that took every signature of the commit in as a vote. Nothing
to read where the driver reports no such counters, or nothing was looked
up."""


def read(ctx):
    c = ctx.result["counters"]
    hits, misses = (c.get("sigcache_hits_commit"),
                    c.get("sigcache_misses_commit"))
    if hits is None or misses is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
