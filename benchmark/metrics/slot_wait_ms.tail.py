"""Time (ms) the dispatch thread waited for a pipeline slot, summed
`pipeline.slot_wait` spans per pipelined dispatch window
(benchmark/inside.py)."""

from benchmark.inside import slot_wait_ms


def read(ctx):
    return slot_wait_ms(ctx.docs)
