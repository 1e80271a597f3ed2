"""Host milliseconds of the LM cascade's prompt work per 1,000 real prompt
tokens decided in the window: the program's profiler phases
``cascade.weak_pass`` (weak stack and chunked features, read back) and
``cascade.prefill`` (the chosen stack's prefill, up to its first tokens)."""


def read(ctx):
    ls = ctx.layer_seconds
    s = ls.get("weak_pass", 0.0) + ls.get("prefill", 0.0)
    if not s or not ls.get("prompt_tokens"):
        return None
    return s * 1e6 / ls["prompt_tokens"]
