"""`chunks_per_readback.*`: RLC chunks the program dispatched for each
read-back of their verdicts (ops/ed25519.py `_verify_batch_loop`: the
`chunks` the program sets on its `ed25519.readback` spans, one span from
a call's last dispatch to its last verdict read), summed over the
window's spans that read any, over their number. A tile of 3,200 lanes
is 7 chunks of 512: 7.0 says the device ran a tile's chunks back to back
while the host prepared the next, 1 that the loop reads each chunk back
before it prepares the next. Nothing to read where the program opens no
such span (before PR 35)."""


def read(ctx):
    chunks = [s["attrs"]["chunks"] for s in ctx.spans
              if s["name"] == "ed25519.readback"
              and s.get("attrs", {}).get("chunks", 0) > 0]
    if not chunks:
        return None
    print(f"[layer] ed25519.readback: {sum(chunks)} chunks in "
          f"{len(chunks)} read-backs", flush=True)
    return sum(chunks) / len(chunks)
