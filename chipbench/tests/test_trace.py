"""The trace reduction, on a slice of a trace recorded on a TPU v5e.

``data/v5e_pkt_scale8.json`` is the first 60 ms of a traced window of two
``truss_pkt`` calls on a scale-8 Kronecker graph (chip run), as
``trace.load`` reduced it, with the operations' HLO text cut to their
names and host events under 0.1 ms dropped.
"""

import json
import pathlib

import numpy as np
import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data" / "v5e_pkt_scale8.json"


@pytest.fixture()
def reduced():
    r = json.loads(DATA.read_text())
    trace._name_by_module(r["ops"], r["modules"])
    return r


def test_busy_time_is_the_union_of_device_operations(reduced):
    lo, hi = reduced["window"]
    # a 1-microsecond timeline, marked op by op, is the plain union
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for s, e, _ in reduced["ops"]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    busy = trace.busy_ns(reduced)
    assert 0 < busy < hi - lo
    assert busy / 1000 == pytest.approx(grid.sum(), rel=0.02)


def test_program_time_sums_the_named_jits(reduced):
    support, runs = trace.module_ns(reduced, {"_support_device_jit"})
    assert runs == 1
    assert support / 1e6 == pytest.approx(15.568, abs=0.01)
    tables, runs = trace.module_ns(reduced, {"_build_peel_table_dev"})
    assert runs == 1 and tables / 1e6 == pytest.approx(8.694, abs=0.01)
    assert trace.module_ns(reduced, {"_renamed_jit"}) == (0.0, 0)


def test_operations_are_named_by_their_program(reduced):
    top = trace.top_ops(reduced)
    assert len(top) == 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert all("/" in name for name, _ in top)
    assert top[0][0].startswith("_peel_segment_jit/")


def test_idle_gaps_are_longest_first_and_named(reduced):
    gaps = trace.idle_gaps(reduced)
    assert gaps and len(gaps) <= 10
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert all(name.startswith("chipbench.") for name, _ in gaps)
    lo, hi = reduced["window"]
    assert sum(g for _, g in gaps) <= (hi - lo - trace.busy_ns(reduced)) / 1e9


def test_clip_merges_overlaps_inside_the_window():
    got = trace.clip([[0, 5, "a"], [3, 8, "b"], [10, 12, "c"], [20, 30, "d"]],
                     2, 11)
    assert got == [(2, 8), (10, 11)]
