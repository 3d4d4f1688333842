"""`ts_prefix_reuse_share.*`: of the CommitSigs the sequential light
client's saves encoded over the window in a commit's one pass
(`light.client.tile_stats()` `sig_encodings`, from
`types/block.SIG_TS_PREFIX`), the share whose timestamp's seconds field
an earlier lane of the same commit had built, in %. On a chain whose
precommits of a height share their second, all lanes of a commit but
the first; less where they straddle a second. Nothing to read where the
run reports no such counter (a program that keeps none), or no lane was
encoded."""


def read(ctx):
    c = ctx.result["counters"]
    reused, encoded = (c.get("light_sig_ts_prefix_reused"),
                       c.get("light_sig_encodings"))
    if reused is None or not encoded:
        return None
    return 100.0 * reused / encoded
