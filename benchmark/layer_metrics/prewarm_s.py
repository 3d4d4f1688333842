"""`prewarm_s`: seconds `prewarm_verify_kernels` took in set-up, by the
host clock: tracing, Mosaic lowering and, in a first run, compilation of
the node bucket's kernel pair. Moves `setup_s`."""


def read(ctx):
    value = ctx.boot.get("prewarm_s", 0.0)
    return value if value > 0 else None
