"""`valset_encode_reuse_share.*`: of the validator-set encodings the
window's apply stage asked for (`state/state.py` `_valset_to_json`:
`StateStore.save` asks four a block, the three sets of the `State` and
the `vals:<height>` index), the share answered from the set's memo
(`VALSET_ENCODINGS`, one count a call). Read from the deltas the program
sets on its `pipeline.apply` spans; both sums are printed, so that the
two together can be held against four a block applied (`facts.blocks`)
and `computed` against one a block on a set that does not change: 75 %.
0 means the memo is dead, 100 that it is stale. Nothing to read where no
span carries the attributes (before PR 32)."""


def read(ctx):
    computed = reused = 0
    for span in ctx.spans:
        attrs = span.get("attrs", {})
        if span["name"] == "pipeline.apply" \
                and "valset_enc_computed" in attrs:
            computed += attrs["valset_enc_computed"]
            reused += attrs["valset_enc_reused"]
    if not computed + reused:
        return None
    print(f"[layer] validator-set encodings: computed {computed} "
          f"reused {reused} (blocks {ctx.result['facts'].get('blocks')})",
          flush=True)
    return 100.0 * reused / (computed + reused)
