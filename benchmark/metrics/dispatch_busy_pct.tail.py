"""How busy (%) the service's dispatch thread was over the window:
100 x (1 - Δloop_wait_ns / Δloop_ns) of `svc.stats()` (benchmark/
inside.py)."""

from benchmark.inside import dispatch_busy_pct


def read(ctx):
    return dispatch_busy_pct(ctx.stats0, ctx.stats1)
