"""Arithmetic of the readers that look inside a dispatch window
(benchmark/metrics/window_unspanned_pct.*, slot_wait_ms.tail,
completer_ms.tail, dispatch_busy_pct.tail).

A dispatch window is a `dispatch` span of the program's traces. Its
descendants are the spans under it; the gather of a pipelined window
(the `coalesce` span, a child of the request root that starts with the
window) is counted as covering the window too. Spans are deduplicated
per (process, span id) and measured by interval union, as in
benchmark/spans.py. Each reader returns None where the program records
nothing for it to read."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmark import spans as S

# the completer's host work of a pipelined window
COMPLETER_SPANS = ("knn.canonical", "merge")


def _proc(doc: dict) -> str:
    return str(doc.get("trace_id", "")).split("-", 1)[0]


def _window_of(spans: Dict[tuple, dict]) -> Dict[tuple, tuple]:
    """(process, span id) -> (process, id) of the nearest `dispatch`
    ancestor, for every span under a window."""
    out: Dict[tuple, tuple] = {}
    for (p, sid), s in spans.items():
        parent = spans.get((p, s.get("parent")))
        while parent is not None:
            if parent["name"] == "dispatch":
                out[(p, sid)] = (p, parent["id"])
                break
            parent = spans.get((p, parent.get("parent")))
    return out


def window_unspanned(docs: List[dict]) -> Optional[dict]:
    """Per process, the union of dispatch-window time and the part of
    it that no descendant span (nor the window's own gather) covers."""
    spans = S.unique_spans(docs)
    windows = {k: s for k, s in spans.items()
               if s["name"] == "dispatch" and s["t1_ns"] > s["t0_ns"]}
    if not windows:
        return None
    cover: Dict[str, List[Tuple[int, int]]] = {}

    def add(p, s, w):
        iv = S.clamp(s["t0_ns"], s["t1_ns"], w["t0_ns"], w["t1_ns"])
        if iv is not None:
            cover.setdefault(p, []).append(iv)

    for k, wk in _window_of(spans).items():
        if wk in windows:
            add(k[0], spans[k], windows[wk])
    for d in docs:
        p = _proc(d)
        ss = d.get("spans", ())
        gathers = [s for s in ss if s["name"] == "coalesce"]
        for s in ss:
            w = windows.get((p, s["id"])) if s["name"] == "dispatch" \
                else None
            if w is not None:
                for g in gathers:
                    add(p, g, w)
    wins: Dict[str, List[Tuple[int, int]]] = {}
    for (p, _), w in windows.items():
        wins.setdefault(p, []).append((w["t0_ns"], w["t1_ns"]))
    win_ns = sum(S.union_ns(v) for v in wins.values())
    cov_ns = sum(S.union_ns(v) for v in cover.values())
    if win_ns <= 0:
        return None
    return {"windows": len(windows), "window_ns": win_ns,
            "unspanned_ns": max(win_ns - cov_ns, 0)}


def window_unspanned_pct(docs: List[dict]) -> Optional[float]:
    g = window_unspanned(docs)
    return 100.0 * g["unspanned_ns"] / g["window_ns"] if g else None


def _pipelined_windows(spans: Dict[tuple, dict]) -> set:
    return {k for k, s in spans.items() if s["name"] == "dispatch"
            and (s.get("attrs") or {}).get("pipelined")}


def slot_wait_ms(docs: List[dict]) -> Optional[float]:
    """Summed `pipeline.slot_wait` time (ms) per pipelined window."""
    spans = S.unique_spans(docs)
    waits = [s["t1_ns"] - s["t0_ns"] for s in spans.values()
             if s["name"] == "pipeline.slot_wait"]
    wins = _pipelined_windows(spans)
    if not waits or not wins:
        return None
    return sum(waits) / 1e6 / len(wins)


def completer_ms(docs: List[dict]) -> Optional[float]:
    """Mean per pipelined window of the union of its `knn.canonical`
    and `merge` spans and its `window.finish` (attr `window`: the
    window's span id): the completer thread's host time, ms."""
    spans = S.unique_spans(docs)
    wins = _pipelined_windows(spans)
    per: Dict[tuple, List[Tuple[int, int]]] = {}
    finishes = 0
    for k, s in spans.items():
        if s["name"] == "window.finish":
            w = (k[0], (s.get("attrs") or {}).get("window"))
            finishes += 1
            if w in wins:
                per.setdefault(w, []).append((s["t0_ns"], s["t1_ns"]))
    if not finishes or not wins:
        return None
    for k, w in _window_of(spans).items():
        if w in wins and spans[k]["name"] in COMPLETER_SPANS:
            s = spans[k]
            per.setdefault(w, []).append((s["t0_ns"], s["t1_ns"]))
    return sum(S.union_ns(v) for v in per.values()) / 1e6 / len(wins)


def dispatch_busy_pct(stats0: dict, stats1: dict) -> Optional[float]:
    """100 x (1 - Δloop_wait_ns / Δloop_ns) of the service's dispatch
    loop between two `stats()` snapshots."""
    if "loop_ns" not in stats0 or "loop_ns" not in stats1:
        return None
    loop = stats1["loop_ns"] - stats0["loop_ns"]
    wait = stats1.get("loop_wait_ns", 0) - stats0.get("loop_wait_ns", 0)
    if loop <= 0:
        return None
    return 100.0 * (1.0 - wait / loop)
