"""The ONE shape at which tier-1 compiles the single-device ed25519
kernels (jitted `verify_rlc_kernel` / `verify_kernel`) on XLA:CPU.

With no persistent cache (tests/conftest.py) a process pays 2-3 minutes
for its first sight of every (kernel, lanes, hash blocks) variant, and
the compile hardly shortens with fewer lanes (123 s + 83 s at 8 lanes,
174 s + 82 s at 64), so what counts is that every file asks for the same
variant: a worker then pays for the pair once, whichever files
`--dist loadfile` hands it. 8 lanes is the smallest width that is still
a batch (chunking, coalescing and attribution all have room), and a
message of up to 128 bytes — a vote's sign-bytes are ~107 — fills the
same 2 SHA-512 blocks as the 64-byte bucket (ops/ed25519.prepare_batch:
(64 + cap + 17 + 127) // 128).

A test whose point is the kernel reaches the pair by passing
KERNEL_LANES as its batch size or bucket, or by handing a size-less
verifier 5-8 signatures (it takes the next power of two), with no
message over KERNEL_MSG_CAP. A test whose point is only that a batch is
verified locally hands the size-less verifier CLAMPED_LANES signatures:
over 64 lanes a CPU backend verifies natively and compiles nothing
(the clamp of crypto/keys.Ed25519BatchVerifier.verify, which
test_blocksync.py and test_mesh.py also stay under).

A module of its own, not names in conftest.py: a whole run imports
tests/benchmark_harness/conftest.py under the module name `conftest`
too, so `from conftest import ...` is whichever came last.
"""

KERNEL_LANES = 8
KERNEL_MSG_CAP = 128
CLAMPED_LANES = 65
