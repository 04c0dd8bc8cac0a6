"""The readers that look inside a dispatch window (benchmark/inside.py
and the five metric files over it), on synthetic traces and counters:
each number by hand, and None where the program records nothing."""

import types

import pytest

from bench_harness_util import REPO  # noqa: F401  (puts the repo on the path)
from benchmark import inside as I

MS = 1_000_000


def sp(name, sid, parent, t0, t1, **attrs):
    s = {"name": name, "id": sid, "parent": parent,
         "t0_ns": t0 * MS, "t1_ns": t1 * MS, "thread": 1}
    if attrs:
        s["attrs"] = attrs
    return s


def doc(spans, proc="p", seq=1, root=1):
    return {"trace_id": f"{proc}-{seq}", "name": "query",
            "root": {"name": "query", "id": root, "parent": None,
                     "t0_ns": 0, "t1_ns": 100 * MS, "thread": 1},
            "spans": spans}


def pipelined_lead():
    """One pipelined window [10, 30]: gather [10, 12] (the root's
    `coalesce`), named children covering [12, 28] but [20, 21], and
    the finish [30, 32] after the window closed."""
    return doc([
        sp("coalesce", 2, 1, 10, 12),
        sp("window.open", 4, 3, 12, 13),
        sp("pipeline.slot_wait", 5, 3, 13, 15),
        sp("ring.slot", 6, 3, 15, 16),
        sp("kernel.dispatch", 7, 3, 16, 18),
        sp("pipeline.pending", 8, 3, 18, 20),
        sp("device.sync", 9, 3, 21, 25),
        sp("knn.canonical", 10, 3, 25, 26),
        sp("merge", 11, 3, 26, 28),
        sp("dispatch", 3, 1, 10, 30, pipelined=True, batch=2),
        sp("window.finish", 12, 1, 30, 32, window=3),
    ])


def rider_of(lead, seq=2):
    """A coalesced rider: adopted copies of the lead's window spans,
    ids kept, re-parented to its own root."""
    spans = [dict(s, parent=(50 if s["parent"] == 1 else s["parent"]))
             for s in lead["spans"] if s["name"] != "window.finish"]
    return doc(spans, seq=seq, root=50)


class TestWindowUnspanned:
    def test_gather_counts_and_the_gaps_do_not(self):
        # window [10, 30] = 20 ms; unnamed: [20, 21] and [28, 30]
        g = I.window_unspanned([pipelined_lead()])
        assert g == {"windows": 1, "window_ns": 20 * MS,
                     "unspanned_ns": 3 * MS}
        assert I.window_unspanned_pct([pipelined_lead()]) == \
            pytest.approx(15.0)

    def test_adopted_copies_count_once(self):
        lead = pipelined_lead()
        docs = [lead, rider_of(lead), rider_of(lead, seq=3)]
        assert I.window_unspanned_pct(docs) == pytest.approx(15.0)

    def test_grandchildren_cover_and_children_clamp(self):
        # serial window [40, 60]: plan [40, 45] with a grandchild
        # [44, 50] under it (a descendant covers wherever it lies in the
        # window), and a child running past the window's end (clamped)
        d = doc([
            sp("coalesce", 20, 1, 30, 40),
            sp("dispatch", 21, 1, 40, 60, batch=1),
            sp("plan", 22, 21, 40, 45),
            sp("band.refine", 23, 22, 44, 50, rows=3),
            sp("merge", 24, 21, 55, 70),
        ])
        # covered [40, 50] and [55, 60]: 5 of 20 ms unnamed; the serial
        # gather [30, 40] lies before the window and covers none of it
        assert I.window_unspanned_pct([d]) == pytest.approx(25.0)

    def test_overlapping_windows_by_union_per_process(self):
        a = doc([sp("dispatch", 3, 1, 0, 10, pipelined=True),
                 sp("device.sync", 4, 3, 0, 10)], seq=1)
        b = doc([sp("dispatch", 13, 11, 5, 20, pipelined=True),
                 sp("device.sync", 14, 13, 5, 15)], seq=2, root=11)
        # union of windows [0, 20]; named [0, 15]
        assert I.window_unspanned_pct([a, b]) == pytest.approx(25.0)
        # another process's spans never cover this one's windows
        c = doc([sp("dispatch", 3, 1, 0, 10, pipelined=True)], proc="q")
        g = I.window_unspanned([a, b, c])
        assert g["window_ns"] == 30 * MS and g["unspanned_ns"] == 15 * MS

    def test_nothing_to_read(self):
        assert I.window_unspanned_pct([]) is None
        assert I.window_unspanned_pct(
            [doc([sp("queue.wait", 2, 1, 0, 5)])]) is None


class TestCompleterAndSlots:
    def test_slot_wait_per_pipelined_window(self):
        lead = pipelined_lead()
        second = doc([sp("dispatch", 30, 1, 40, 60, pipelined=True),
                      sp("pipeline.slot_wait", 31, 30, 40, 44)], seq=9)
        serial = doc([sp("dispatch", 40, 1, 70, 80, batch=1)], seq=10)
        # (2 + 4) ms over two pipelined windows; the serial one is not
        # a pipelined window
        assert I.slot_wait_ms([lead, rider_of(lead), second, serial]) \
            == pytest.approx(3.0)

    def test_completer_is_canonical_merge_and_finish(self):
        lead = pipelined_lead()
        # canonical [25, 26] + merge [26, 28] + finish [30, 32] = 5 ms
        assert I.completer_ms([lead, rider_of(lead)]) == pytest.approx(5.0)

    def test_parent_program_reads_nothing(self):
        """A program without the new spans (the parent of this change):
        the window readers that need them return None, never 0."""
        old = doc([s for s in pipelined_lead()["spans"]
                   if s["name"] not in ("pipeline.slot_wait",
                                        "window.finish", "knn.canonical",
                                        "window.open", "pipeline.pending")])
        assert I.slot_wait_ms([old]) is None
        assert I.completer_ms([old]) is None
        assert I.window_unspanned_pct([old]) is not None


class TestDispatchBusy:
    def test_busy_share_from_counter_deltas(self):
        s0 = {"loop_ns": 1_000, "loop_wait_ns": 400}
        s1 = {"loop_ns": 11_000, "loop_wait_ns": 2_900}
        assert I.dispatch_busy_pct(s0, s1) == pytest.approx(75.0)

    @pytest.mark.parametrize("s0, s1", [
        ({}, {}),
        ({"dispatches": 3}, {"dispatches": 9}),
        ({"loop_ns": 5, "loop_wait_ns": 1}, {"loop_ns": 5,
                                             "loop_wait_ns": 1}),
    ])
    def test_nothing_to_read(self, s0, s1):
        assert I.dispatch_busy_pct(s0, s1) is None


NEW = {
    "window_unspanned_pct.tail": "ais-knn-open",
    "window_unspanned_pct.rate": "gdelt-bbox-open",
    "slot_wait_ms.tail": "ais-knn-open",
    "completer_ms.tail": "ais-knn-open",
    "dispatch_busy_pct.tail": "ais-knn-open",
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_is_found_by_name_and_reads_a_run(name):
    from benchmark.manifest import resolve

    cell = resolve(NEW[name])
    entry = {m["name"]: m for m in cell.per_layer}[name]
    assert entry["layer"] == "dispatch"
    reader = cell.readers[name]
    lead = pipelined_lead()
    ctx = types.SimpleNamespace(
        docs=[lead, rider_of(lead)],
        stats0={"loop_ns": 0, "loop_wait_ns": 0},
        stats1={"loop_ns": 10, "loop_wait_ns": 4})
    assert reader.read(ctx) is not None
    empty = types.SimpleNamespace(docs=[], stats0={}, stats1={})
    assert reader.read(empty) is None
