"""The completer thread's host time (ms) per pipelined dispatch window:
the union of its `knn.canonical`, `merge` and `window.finish` spans
(benchmark/inside.py)."""

from benchmark.inside import completer_ms


def read(ctx):
    return completer_ms(ctx.docs)
