"""model.mfu: operations the model needs for every prompt and output token
the window processed, over the window's length times the chip's peak.

Counted from the config's shapes by the cell's family
(``bench/counts/<family>.py``, ``ctx.counts``): for ``decoder``, two per
matmul weight per token, attention over each token's live context, one
row of logits per output token; padding is not work."""


def read(ctx):
    c, s = ctx.counts, ctx.s
    flops = sum(c.prefill_flops(s, p) for r in ctx.rounds for p in r.prompts)
    flops += sum(c.decode_flops(s, k) for r in ctx.rounds for k in r.contexts)
    if not flops or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
