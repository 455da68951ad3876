"""paged_attn_roofline: the least time the chip could take for the paged
decode attention of the window's decode steps, over the device time of
the ``paged_decode_attention`` kernel's events
(``kernels/paged_decode_attention.py``).

The bound is the larger of the bytes it must move over HBM bandwidth and
its operations over peak compute, both counted by the cell's family
(``bench/counts/<family>.py``, ``ctx.counts``). For ``decoder`` the bytes
are the K and V rows of every live context, queries and outputs; at G
query heads per KV head it does about G operations per byte, far under
the v5e ridge, so the bytes bound it."""

KERNEL = "paged_decode_attention"


def read(ctx):
    c, s = ctx.counts, ctx.s
    t = ctx.trace.kernel_s.get(KERNEL, 0.0)
    if not t:
        return None
    nbytes = sum(c.paged_attn_bytes(s, r.contexts) for r in ctx.rounds)
    flops = sum(c.paged_attn_flops(s, r.contexts) for r in ctx.rounds)
    bound = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * bound / t
