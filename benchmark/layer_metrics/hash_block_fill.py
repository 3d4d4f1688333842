"""`hash_block_fill.*`: of the SHA-512 blocks the kernel computed in
the window's chunks (`ops.ed25519.batch_stats()` `hash_blocks_dispatched`:
every lane of a chunk, padding in, at the chunk's block axis), the share
the real lanes' messages need (`hash_blocks_real`), in %: the device's
hashing that was real work. Padding lanes, a vote lane in a chunk shaped
for a 2 KiB extension and an extension in a wider bucket than its need
all read lower. Nothing to read where the program does not count hash
blocks, or no chunk was verified."""


def read(ctx):
    c = ctx.result["counters"]
    real, dispatched = (c.get("batch_hash_blocks_real"),
                        c.get("batch_hash_blocks_dispatched"))
    if real is None or not dispatched:
        return None
    return 100.0 * real / dispatched
