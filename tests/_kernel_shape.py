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
KERNEL_LANES as its batch size or bucket to `ops.ed25519.verify_batch`,
`verify_lanes` or a device server, with no message over KERNEL_MSG_CAP.
Nothing else reaches a kernel: on a CPU backend the program's own
routes verify natively at every width and compile nothing
(`crypto/keys.kernel_width()` is 0). A test whose point is only that a
batch is verified locally hands the verifier LOCAL_LANES signatures.

A module of its own, not names in conftest.py: a whole run imports
tests/benchmark_harness/conftest.py under the module name `conftest`
too, so `from conftest import ...` is whichever came last.
"""

KERNEL_LANES = 8
KERNEL_MSG_CAP = 128
LOCAL_LANES = 65
