"""`set_hash_reuse_share.*`: of the headers the sequential light client
trusted over the window (`light.client.tile_stats()`), the share whose
validator set took the hash of the header before's, equal member for
member (`ValidatorSet.adopt_hash_of`, PR 37), instead of computing its
own merkle root, in %. On a chain whose set never changes, all but the
target's, which the client hashes when it fetches it. Nothing to read
where the driver reports no such counter (before PR 37), or no header
was trusted."""


def read(ctx):
    c = ctx.result["counters"]
    reused, headers = (c.get("light_set_hashes_reused"),
                       c.get("light_headers"))
    if reused is None or not headers:
        return None
    return 100.0 * reused / headers
