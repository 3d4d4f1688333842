"""`sigcache_keyed_insert_share.*`: of the lanes the verified-signature
cache took in during the window's catch-up settles (`pipeline.settle`)
and light-client saves (`light.save`), the share inserted with the key
their lookup had computed (`SigCache.insert`) rather than hashed again
(`SigCache.add`), in %. Read from the deltas of the cache's own counts
that the program sets on those spans (`sigcache_inserted`,
`sigcache_inserted_keyed`); both sums are printed. Nothing to read where
no span carries them (a program that keeps no such counts), or nothing
was inserted."""

SPANS = ("pipeline.settle", "light.save")


def read(ctx):
    inserted = keyed = 0
    for span in ctx.spans:
        attrs = span.get("attrs", {})
        if span["name"] in SPANS and "sigcache_inserted" in attrs:
            inserted += attrs["sigcache_inserted"]
            keyed += attrs["sigcache_inserted_keyed"]
    if not inserted:
        return None
    print(f"[layer] sigcache inserts: {inserted} lanes, {keyed} with their "
          f"lookup's key", flush=True)
    return 100.0 * keyed / inserted
