"""Programs JAX built while the window ran (a backend compile, or a read
from the persistent cache after tracing and lowering), plus the retraces
of the program's registered jits (``repro_jit_retraces_total``) in the
same time: a retrace that compiles counts once in each, one that finds
its program compiled counts once.  Each retrace of a jitted scoring
function and each rebuilt ``shard_map`` shows here."""


def read(ctx):
    return ctx.compiles + ctx.retraces
