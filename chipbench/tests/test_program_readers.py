"""The readers of the program's own spans and counters, on a small trace
recorded on the CPU around two ``truss_pkt`` calls."""

import sys

import numpy as np
import pytest

from chipbench import program, reference, trace, work
from chipbench.readers import span_scan_pct, span_self_ms, trace_us_per_count

SUBLEVEL_US = {"jits": ["_peel_segment_jit"], "span": "pkt.peel_segment",
               "counter": "sublevels"}
SCAN = {"span": "pkt.peel_segment", "probes": "peel_probes"}
#: compacting at every level boundary, so compaction spans are present
COMPACT = dict(compact_frac=0.99, compact_min=0)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from repro import spans
    from repro.core import truss_pkt
    from repro.graphs.gen import rmat_edges

    E = rmat_edges(6, edge_factor=5, seed=3)
    truss_pkt(E, **COMPACT)                    # compiles, untraced
    spans.drain()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    tracer = trace.Tracer(log_dir)
    tracer.start()
    for _ in range(2):
        truss_pkt(E, **COMPACT)
    tracer.stop()
    truss_pkt(E, **COMPACT)                    # after the trace: not read
    obs = {"decompositions": 2,
           "peel_probes": work.peel_probes(reference.canonical(E))}
    yield trace.load(log_dir), obs, spans.records()
    spans.drain()


def test_only_the_traced_decompositions_are_read(traced):
    reduced, obs, recs = traced
    got = program.traced_spans(obs)
    assert len({r.decomp for r in got}) == 2
    assert all(r.traced for r in got)
    assert len(got) < len(recs)
    assert program.traced_spans({**obs, "decompositions": 3}) is None


def test_sublevel_time_divides_peel_device_time_by_sublevels(traced):
    reduced, obs, _ = traced
    us = trace_us_per_count.read(SUBLEVEL_US, obs, reduced)
    subs = sum(r.attrs["sublevels"] for r in program.traced_spans(obs)
               if r.name == "pkt.peel_segment")
    ns, runs = trace.module_ns(reduced, {"_peel_segment_jit"})
    assert runs > 0 and subs > 0
    assert us == pytest.approx(ns / 1e3 / subs)
    assert trace_us_per_count.read(SUBLEVEL_US, obs, None) is None


def test_scan_share_is_probes_over_rows_scanned(traced):
    reduced, obs, _ = traced
    segs = [r for r in program.traced_spans(obs)
            if r.name == "pkt.peel_segment"]
    rows = sum(r.attrs["chunk_visits"] * r.attrs["chunk"] for r in segs)
    pct = span_scan_pct.read(SCAN, obs, reduced)
    assert pct == pytest.approx(100.0 * obs["peel_probes"] * 2 / rows)
    assert 0.0 < pct


@pytest.mark.parametrize("name", ["truss_pkt.prep", "pkt.compact"])
def test_span_self_time_per_decomposition(traced, name):
    reduced, obs, _ = traced
    got = program.traced_spans(obs)
    mine = [r for r in got if r.name == name]
    assert mine
    ms = span_self_ms.read({"span": name}, obs, reduced)
    assert 0.0 < ms == pytest.approx(
        sum(r.end_ns - r.start_ns for r in mine) / 1e6 / 2)
    # the spans also sit in the trace's host events, on the same thread
    in_trace = [h for h in reduced["host"] if h[2] == f"repro.{name}"]
    assert len(in_trace) == len(mine)


def test_self_time_leaves_out_child_spans(traced):
    reduced, obs, _ = traced
    got = program.traced_spans(obs)
    root = next(r for r in got if r.name == "pkt")
    children = [r for r in got if r.parent == root.id]
    assert children
    assert program.self_ns(root) == (
        root.end_ns - root.start_ns
        - sum(r.end_ns - r.start_ns for r in children))
    assert np.isclose(span_self_ms.read({"span": "pkt"}, obs, reduced),
                      sum(program.self_ns(r) for r in got
                          if r.name == "pkt") / 1e6 / 2)


def test_a_program_without_spans_reads_nothing(traced, monkeypatch):
    reduced, obs, _ = traced
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert program.traced_spans(obs) is None
    for reader, spec in ((trace_us_per_count, SUBLEVEL_US),
                         (span_scan_pct, SCAN),
                         (span_self_ms, {"span": "pkt.compact"})):
        assert reader.read(spec, obs, reduced) is None
