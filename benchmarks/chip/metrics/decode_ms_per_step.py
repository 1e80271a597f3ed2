"""Host milliseconds of the LM cascade's token loop per decode step: the
program's profiler phase ``cascade.decode`` over the steps its decode
programs ran in the window (one per answer token after the first, per
stack a block used)."""


def read(ctx):
    ls = ctx.layer_seconds
    if not ls.get("decode") or not ls.get("decode_steps"):
        return None
    return ls["decode"] * 1e3 / ls["decode_steps"]
