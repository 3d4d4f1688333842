"""`commit_encode_reuse_share.*`: of the `CommitSig` wire encodings the
window's main thread asked for, the share answered from the instance's
memo (`types/block.py` `SIG_ENCODINGS`, one count a signature). Read
from the deltas the program sets on its `pipeline.fetch` and
`pipeline.apply` spans; both sums are printed, so that `computed` can
be held against the signatures the window served (`facts.lanes`): the
node, not the traffic's generator, pays every first encoding. Nothing to
read where no span carries the attributes."""

STAGES = ("pipeline.fetch", "pipeline.apply")


def read(ctx):
    computed = reused = 0
    for span in ctx.spans:
        attrs = span.get("attrs", {})
        if span["name"] in STAGES and "sig_enc_computed" in attrs:
            computed += attrs["sig_enc_computed"]
            reused += attrs["sig_enc_reused"]
    if not computed + reused:
        return None
    print(f"[layer] commit signature encodings: computed {computed} "
          f"reused {reused} (lanes {ctx.result['facts'].get('lanes')})",
          flush=True)
    return 100.0 * reused / (computed + reused)
