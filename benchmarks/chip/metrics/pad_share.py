"""Share of the padded prompt tokens the LM cascade prefilled in the
window that were pads: ``repro_cascade_pad_tokens_total`` over it plus
``repro_cascade_tokens_total{phase="prefill"}`` (the real tokens)."""


def read(ctx):
    ls = ctx.layer_seconds
    real, pads = ls.get("prefill_tokens"), ls.get("pad_tokens")
    if real is None or pads is None or not real + pads:
        return None
    return pads / (real + pads)
