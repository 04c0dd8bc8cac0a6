"""Shared timing helpers for the perf-lab scripts (real-chip runs)."""

from __future__ import annotations

import time

import numpy as np
import jax


def sync(out):
    """Force device completion: fetch one scalar to host."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf[(0,) * leaf.ndim])
    return out


def timeit(fn, repeats=4):
    fn()  # warm / compile
    best = float("inf")
    for _ in range(repeats):
        s = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - s)
    return best
