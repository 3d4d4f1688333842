"""Generator `vote_stream_ext`: `vote_stream`'s signed height stream for
one validator of a chain with vote extensions on from height 1, plus, for
every played validator's precommit of a live height, the signature of
its vote extension: by the plain reference (`cryptography` wheel) over
`reference/canonical_vote_extension.extension_sign_bytes` of the
extension `canonical_vote_extension.extension` derives from the height
and the validator, `vote_extension_bytes` long (configuration).

The stream itself is `vote_stream.make`'s, unchanged: the block, the
proposal, the votes, their bursts and redeliveries. The vote extensions
enable height is no part of the blocks (the header's `consensus_hash`
covers the block size and gas caps alone, reference types/params.go
`HashParams`), so the chain is the one `vote_stream` builds. Each live
row gains `precommit_ext_sigs` (one a played validator, in set order);
the extensions themselves stay out of the payload (149 × 2 KiB a height):
the driver derives them as the app does."""

from __future__ import annotations

from benchmark.generators import vote_stream
from benchmark.reference import canonical_vote_extension as cve
from benchmark.reference import ed25519_ref


def make(params: dict) -> dict:
    out = vote_stream.make(params)
    size = params["config"]["vote_extension_bytes"]
    signers = [ed25519_ref.Signer(s) for s in out["signer_seeds"]]
    addrs = [cve.address(s.pub) for s in signers]
    for row in out["heights"]:
        h = row["height"]
        row["precommit_ext_sigs"] = [
            signer.sign(cve.extension_sign_bytes(
                out["chain_id"], h, 0, cve.extension(h, addr, size)))
            for signer, addr in zip(signers, addrs)]
    out["vote_extension_bytes"] = size
    return out
