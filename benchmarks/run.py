# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry: python -m benchmarks.run [--quick|--smoke]

  table2  — ordering impact on support computation      (paper Table 2)
  table3  — PKT vs WC vs Ros decomposition + GWeps      (paper Table 3)
  table4  — parallel scaling over host devices          (paper Table 4/Fig 5)
  fig4    — phase breakdown per peel mode               (paper Fig 4)
  fig6    — per-level time vs trussness distribution    (paper Fig 6)
  engine  — batched multi-graph throughput (graphs/sec)
  inc     — incremental update vs recompute speedup     (DESIGN.md §9)
  hier    — community-index build/query + label parity  (DESIGN.md §11)
  hillclimb— chunk-policy autotune sweep (feeds auto_chunk, §16)

``--smoke`` is the CI gate: a tiny RMAT graph decomposed by every peel
mode, both table modes, Ros, the batched engine and the numpy oracle;
agreement is asserted (exit 1 on mismatch) and a machine-readable
BENCH_smoke.json is written for workflow artifacts.
"""

import argparse
import json
import sys
import time


def smoke(out_path: str = "BENCH_smoke.json") -> int:
    """Tiny cross-engine agreement gate + timing snapshot. Returns exit code."""
    import numpy as np

    from repro.graphs.gen import rmat_edges
    from repro.graphs.csr import build_csr, relabel, degeneracy_order
    from repro.core import pkt, truss_ros, truss_numpy
    from repro.core.pkt import PEEL_MODES, align_to_input
    from repro.serve.truss_engine import TrussEngine

    E = rmat_edges(6, edge_factor=5, seed=0)
    n = int(E.max()) + 1
    E = relabel(E, degeneracy_order(E, n))
    g = build_csr(E, n)

    report = {"graph": "rmat-6-5", "n": g.n, "m": g.m, "modes": {}, "ok": True}
    ref = truss_numpy(g.El)
    report["t_max"] = int(ref.max(initial=2))

    def check(name, t):
        same = bool(np.array_equal(np.asarray(t, np.int64), ref))
        report["ok"] = report["ok"] and same
        return same

    for mode in PEEL_MODES:
        t0 = time.perf_counter()
        res = pkt(g, mode=mode, phase_timings=True)
        dt = time.perf_counter() - t0
        report["modes"][mode] = {
            "seconds": dt, "agrees": check(f"pkt/{mode}", res.trussness),
            "levels": res.levels, "sublevels": res.sublevels,
            "phases": {k: round(v, 6) for k, v in res.phases.items()},
        }

    # table_mode axis: host-built tables (the parity oracle) vs the default
    # device builders — phase breakdown shows where table-build time lives.
    # Both runs are warm (the executors compiled above), so the numbers
    # compare steady-state table construction, not jit compiles.
    res_np = pkt(g, table_mode="numpy", phase_timings=True)
    res_dev = pkt(g, table_mode="device", phase_timings=True)
    report["table_modes"] = {
        "device": {k: round(v, 6) for k, v in res_dev.phases.items()},
        "numpy": {k: round(v, 6) for k, v in res_np.phases.items()},
        "agrees": (check("pkt/table-numpy", res_np.trussness)
                   and check("pkt/table-device", res_dev.trussness)),
    }

    t0 = time.perf_counter()
    ros = truss_ros(g)
    report["ros"] = {"seconds": time.perf_counter() - t0,
                     "agrees": check("ros", ros)}

    # batched engine: the same graph plus a truncated copy, order-aligned
    # (engine results align to each submission's own row order, so the
    # g.El-ordered oracle is mapped back to E's rows for comparison)
    ref_rows = align_to_input(np.asarray(ref), g, E, n)
    eng = TrussEngine()
    fleet = [E, E[: max(1, g.m // 2)], E]
    outs = eng.map(fleet)
    eng_ok = (np.array_equal(outs[0], ref_rows)
              and np.array_equal(outs[2], ref_rows)
              and outs[1].shape[0] == fleet[1].shape[0])
    eng.map(fleet)  # second pass hits warm buckets → steady-state throughput
    report["engine"] = {"agrees": bool(eng_ok),
                        "graphs_per_sec": eng.throughput,
                        "buckets": len(eng.stats["buckets"])}
    report["ok"] = report["ok"] and eng_ok

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(json.dumps(report, indent=2, sort_keys=True))
    if not report["ok"]:
        print("SMOKE FAILED: engine disagreement", file=sys.stderr)
        return 1
    return 0


def run_benches(benches: dict, only=None) -> list[str]:
    """Run the selected benches, printing their CSV rows.

    Returns the names of the benches that raised or wrote an ``ERROR``
    row; the others still run.
    """
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            for line in fn():
                print(line, flush=True)
                if line.split(",")[1:2] == ["ERROR"]:
                    failed.append(name)
        except Exception as e:  # report, keep going, fail at the end
            print(f"{name},ERROR,{type(e).__name__}:{e}", file=sys.stderr)
            failed.append(name)
    return sorted(set(failed))


def main(argv=None) -> None:
    """CLI entry: run the selected benches; exit 1 if any of them failed."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small graph suite only")
    ap.add_argument("--smoke", action="store_true",
                    help="CI agreement gate on a tiny graph; writes "
                         "BENCH_smoke.json and exits nonzero on mismatch")
    ap.add_argument("--smoke-out", default="BENCH_smoke.json")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benches")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        raise SystemExit(smoke(args.smoke_out))

    from repro.graphs.datasets import GRAPH_SUITE
    suite = GRAPH_SUITE[:5] if args.quick else GRAPH_SUITE
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (table2_support, table3_decomp, table4_parallel,
                            fig4_phases, fig6_levels, engine_bench, inc_bench,
                            hier_bench, hillclimb)
    benches = {
        "table2": lambda: table2_support.run(suite),
        "table3": lambda: table3_decomp.run(suite),
        "table4": lambda: table4_parallel.run(
            suite=("rmat-small", "ba-small") if args.quick
            else ("rmat-small", "ba-small", "er-small"),
            device_counts=(1, 2, 4) if args.quick else (1, 2, 4, 8)),
        "fig4": lambda: fig4_phases.run(suite),
        "fig6": lambda: fig6_levels.run(),
        "engine": lambda: engine_bench.run(
            n_graphs=12 if args.quick else 24),
        "hillclimb": lambda: hillclimb.rows(quick=args.quick),
        "inc": lambda: inc_bench.rows(quick=args.quick),
        "hier": lambda: hier_bench.rows(quick=args.quick),
    }
    print("name,us_per_call,derived")
    failed = run_benches(benches, only)
    if failed:
        print(f"benches failed: {', '.join(failed)}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
