"""sched.batch_mean: requests that took a decode token per engine step in
the window (scheduler, ``serving/scheduler.py``). Counted by the client
from the tokens it receives, over the engine's own step counter."""


def read(ctx):
    steps = sum(r.steps for r in ctx.rounds if r.contexts)
    if not steps:
        return None
    return sum(len(r.contexts) for r in ctx.rounds) / steps
