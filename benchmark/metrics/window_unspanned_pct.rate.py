"""Share (%) of dispatch-window time, by interval union per process,
that no span under the window names (benchmark/inside.py)."""

from benchmark.inside import window_unspanned_pct


def read(ctx):
    return window_unspanned_pct(ctx.docs)
