"""Model operations of every token processed in the traced window (prompt
tokens computed by prefill, decoded tokens, and the LM head of each row
that emitted a token; prompt tokens served from the prefix cache are not
processed) over the window times the chip's bf16 peak."""


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    ticks = ctx.traced_ticks()
    if not ticks:
        return None
    flops = 0
    for t in ticks:
        if t.kind == "decode":
            flops += ctx.work.decode_step(ctx.spec, t.kv_lens, t.substeps)[0]
        flops += ctx.work.prefill_flops(ctx.spec, t.prefill_spans, t.first_tokens)
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peak["bf16_flops_per_s"])
