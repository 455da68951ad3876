"""pool.used: mean share of the KV pool's blocks in use after each
scheduling round of the window (``kvcache/paged.py`` BlockManager)."""


def read(ctx):
    if not ctx.rounds:
        return None
    return 100.0 * sum(r.pool_used for r in ctx.rounds) / len(ctx.rounds)
