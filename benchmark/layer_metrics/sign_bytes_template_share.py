"""`sign_bytes_template_share.*`: of the `Commit.vote_sign_bytes` calls
of the window's marshal stage, the share served from the commit's
template rather than building it (`types/block.py`
`SIGN_BYTES_TEMPLATES`, one count a call: a commit's first lane builds
the head and tail its other lanes reuse). Read from the deltas the
program sets on its `pipeline.marshal` spans; both sums are printed, so
that `built` can be held against the commits the window marshalled and
the two together against its lanes (`facts.lanes`). Nothing to read where
no span carries the attributes (before PR 30)."""


def read(ctx):
    built = served = 0
    for span in ctx.spans:
        attrs = span.get("attrs", {})
        if span["name"] == "pipeline.marshal" \
                and "sign_bytes_templates" in attrs:
            built += attrs["sign_bytes_templates"]
            served += attrs["sign_bytes_templated"]
    if not built + served:
        return None
    print(f"[layer] sign-bytes templates: built {built} lanes served from "
          f"one {served} (lanes {ctx.result['facts'].get('lanes')})",
          flush=True)
    return 100.0 * served / (built + served)
