"""``community.kron11`` off the chip: a whole run at the rehearsal size,
the same run with the served path broken underneath, and the cell's
readers on a hand-built ring of the program's spans."""

import json

import pytest

from chipbench import faults_community, run
from chipbench.readers import (round_span_ratio, round_span_self_ms,
                               round_span_share, trace_roofline_bytes)
from repro.spans import Span

CELL = "community.kron11"


def result(capsys, seed: int) -> tuple[dict, str]:
    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "1", "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_rehearsal_is_correct_and_compiles_nothing_in_its_window(capsys):
    out, log = result(capsys, 2**33 + 17)
    assert out["correct"] is True and out["rehearse"] is True
    assert set(out["checks"]) == {"wrong_trussness", "wrong_communities"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert out["metrics"]["decomp_s"]["value"] > 0
    assert "programs obtained inside it 0" in log


def test_traced_rehearsal_reports_the_layers_it_shares_with_oneshot(capsys):
    """The full rebuilds are decompositions: the support, table and peel
    metrics of ``oneshot.kron11`` and the device's idle share read here
    too, per rebuild."""
    rc = run.main(["--workload", CELL, "--seed", str(2**33 + 19),
                   "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    got = out["metrics"]
    assert {"support_dev_ms", "tables_dev_ms", "peel_dev_ms",
            "idle_pct.decomp", "rebuild_dev_ms"} <= got.keys()
    parts = sum(got[m]["value"] for m in
                ("support_dev_ms", "tables_dev_ms", "peel_dev_ms"))
    # every traced round here is a full rebuild, so the rebuild's device
    # time a round is the three layers' a rebuild
    assert parts == pytest.approx(got["rebuild_dev_ms"]["value"], rel=1e-9)
    assert 0 < got["idle_pct.decomp"]["value"] < 100


@pytest.mark.parametrize("fault,check", [
    (faults_community.fault_trussness_row, "wrong_trussness"),
    (faults_community.fault_missing_edge, "wrong_communities"),
    (faults_community.control_community, "wrong_communities"),
], ids=lambda x: getattr(x, "__name__", x))
def test_broken_path_is_not_correct(capsys, fault, check):
    with fault():
        out, _ = result(capsys, 11)
    assert out["correct"] is False
    assert out["checks"][check]["value"] > 0


# -------------------------------------------------------------- readers ----

def _ring():
    """Two traced rounds: an update and two queries each, in ms."""
    recs, ids = [], iter(range(1, 1000))

    def span(name, start, end, parent=None, **attrs):
        sid = next(ids)
        rec = Span(sid, name, None if parent is None else parent.id,
                   sid if parent is None else parent.decomp,
                   int(start * 1e6), int(end * 1e6), True, attrs)
        if parent is not None:
            parent.children.append(rec)
        recs.append(rec)
        return rec

    t = 0.0
    for mode, cand, path in ((2, 30, "host"), (1, 10, "device")):
        up = span("inc.update", t, t + 100, inserted=5, deleted=5, m=100,
                  mode=mode, insert_candidates=cand)
        span("inc.deletions", t, t + 10, up)               # self 10
        ins = span("inc.insertions", t + 10, t + 40, up)   # self 20
        span("inc.region_peel", t + 20, t + 30, ins, path=path)
        full = span("inc.full_rebuild", t + 40, t + 90, up)
        span("pkt", t + 40, t + 80, full)
        span("inc.triangle_list", t + 80, t + 88, full)    # self 8
        # inc.update's own: 100 - 10 - 30 - 50 = 10
        for q in range(2):
            c = span("engine.community", t + 100 + 10 * q,
                     t + 108 + 10 * q, k=3, communities=1, edges=4)
            span("hier.level", t + 100 + 10 * q, t + 103 + 10 * q, c, k=3)
        t += 200
    return {"rounds": 2, "traced_spans": recs,
            "traced_roots": {"inc.update": 2, "engine.community": 4}}


SPECS = {
    "full": {"roots": ["inc.update"], "span": "inc.update",
             "counter": "mode", "value": 2},
    "region": {"roots": ["inc.update"], "span": "inc.update",
               "numerator": "insert_candidates", "denominator": "m",
               "where": "inserted"},
    "update": {"roots": ["inc.update"],
               "spans": ["inc.update", "inc.deletions", "inc.insertions",
                         "inc.triangle_list", "inc.region_peel"],
               "only": {"inc.region_peel": {"path": "host"}}},
    "query": {"roots": ["engine.community"], "spans": ["engine.community"]},
}


def test_round_readers_on_a_hand_built_ring():
    obs = _ring()
    assert round_span_share.read(SPECS["full"], obs, None) == 50.0
    assert round_span_ratio.read(SPECS["region"], obs, None) == \
        pytest.approx(20.0)
    # per round: 10 + 10 + 20 + 8, and the first round's host re-peel 10
    assert round_span_self_ms.read(SPECS["update"], obs, None) == \
        pytest.approx((48 + 10 + 48) / 2)
    # two queries a round, 8 ms each less a 3 ms level build
    assert round_span_self_ms.read(SPECS["query"], obs, None) == \
        pytest.approx(10.0)


def test_round_readers_find_nothing_where_the_rounds_disagree():
    obs = _ring()
    short = {**obs, "traced_roots": {"inc.update": 3,
                                     "engine.community": 4}}
    assert round_span_share.read(SPECS["full"], short, None) is None
    assert round_span_self_ms.read(SPECS["update"], short, None) is None
    # a reader of other roots is not affected
    assert round_span_self_ms.read(SPECS["query"], short, None) == \
        pytest.approx(10.0)
    assert round_span_share.read(SPECS["full"],
                                 {**obs, "traced_spans": None}, None) is None
    # a program without the spans (the parent of this cell) reads nothing
    bare = {**obs, "traced_spans": [r for r in obs["traced_spans"]
                                    if r.name.startswith("pkt")]}
    assert round_span_ratio.read(SPECS["region"], bare, None) is None


def test_flood_roofline_divides_counted_bytes_by_device_time():
    reduced = {"window": [0, 10_000_000],
               "modules": [[1_000_000, 2_000_000, "_labelprop"],
                           [3_000_000, 4_000_000, "_peel_segment_jit"]]}
    obs = {"flood_bytes": 819_000, "device_kind": "TPU v5 lite"}
    spec = {"jits": ["_labelprop"], "bytes": "flood_bytes"}
    # 819 kB at 819 GB/s is 1 us, over 1 ms of flood
    assert trace_roofline_bytes.read(spec, obs, reduced) == \
        pytest.approx(0.1)
    assert trace_roofline_bytes.read(spec, obs, None) is None
    assert trace_roofline_bytes.read(
        spec, {**obs, "device_kind": "cpu"}, reduced, rehearse=True) is None
