#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python chipbench/run.py --workload oneshot.kron11 --seed 7 --seconds 51 --trace 0
    JAX_PLATFORMS=cpu python chipbench/run.py --workload oneshot.kron11 \
        --seed 7 --seconds 5 --trace 1 --rehearse

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix and per-layer metrics are files under ``chipbench/`` found by name
(``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.json``).  The traffic file names the driver
(``drivers/<generator>.py``) that builds the inputs from ``--seed``, sets
up and warms the system, runs the measured window and checks what the
window produced against the plain reference (``reference.py``).  A
metric's entry in ``BENCHMARK.json`` is the one place for its unit, layer
and cells; its file holds only what its reader (``readers/<reader>.py``)
needs.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of stdout is one JSON object; the numbers compared
for ``correct`` come last in it and again, each beside its limit, as the
last lines of stderr.  Without a TPU, or with fewer chips than the cell
asks for, the run exits with code 2 and prints no result.  ``--rehearse``
runs a tiny copy of the cell on whatever JAX finds (the CPU here): it
names its platform, carries ``"rehearse": true`` and is never a chip
number.
"""

import time

#: the process's start on the host clock: set-up is measured from here
T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import trace as tracemod  # noqa: E402
from chipbench.common import Cell, CompileCounter, log, load_json  # noqa: E402


def cell_spec(name: str) -> dict:
    """The workload ``name`` with its configuration, traffic and metrics."""
    bench = load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return {
        "workload": w,
        "config": load_json(ROOT / cfg_entry["file"]),
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "end_to_end": e2e,
        "per_layer": [{**m, **load_json(HERE / "metrics" / f"{m['name']}.json")}
                      for m in per_layer],
    }


def shrink(spec: dict) -> dict:
    """The tiny copy of a cell that ``--rehearse`` runs."""
    spec["config"] = {**spec["config"], **spec["config"].get("rehearse", {})}
    spec["traffic"] = {**spec["traffic"],
                       **spec["traffic"].get("rehearse", {})}
    return spec


def device_info(devices, chips: int) -> dict:
    """Platform, kind, chips used and the peak memory of the fullest one."""
    peaks = []
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": max(peaks) if peaks else None}


def per_layer_metrics(spec: dict, cell: Cell, reduced, device_kind: str,
                      rehearse: bool) -> dict:
    """Every per-layer metric of the cell that its reader finds."""
    obs = {**cell.observations(), "device_kind": device_kind}
    out = {}
    for m in spec["per_layer"]:
        reader = importlib.import_module(f"chipbench.readers.{m['reader']}")
        value = reader.read(m, obs, reduced, rehearse=rehearse)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, t_start: float = T_START) -> int:
    """Run one cell once; returns the exit code.

    Set-up is timed from ``t_start``, the process's start unless a caller
    that runs several cells in one process says otherwise.
    """
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="a tiny copy of the cell on any platform")
    args = ap.parse_args(argv)

    spec = cell_spec(args.workload)
    if args.rehearse:
        spec = shrink(spec)
    chips = int(spec["workload"]["chips"])

    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < chips):
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)} after {time.perf_counter() - t_start:.3f}s; "
        f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    driver = importlib.import_module(
        f"chipbench.drivers.{spec['traffic']['generator']}")
    cell: Cell = driver.Driver(spec, seed=args.seed, seconds=args.seconds)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: programs obtained {compiles.programs} "
        f"(cache loads {compiles.cache_loads}, {compiles.seconds:.3f}s)")

    before = compiles.programs
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        cell.tracer = tracemod.Tracer(
            trace_dir, spec["traffic"].get("trace_seconds"))
        t0 = time.perf_counter()
        cell.tracer.start()
        cell.window()
        cell.tracer.stop()
        log(f"window {time.perf_counter() - t0:.3f}s: programs obtained "
            f"inside it {compiles.programs - before}")
        reduced = tracemod.load(trace_dir) if args.trace else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(devices, chips)
    log(f"peak_bytes_in_use: {device['memory_peak_bytes']}")
    cell.close()
    gc.collect()

    checks = cell.check()
    if args.trace:
        metrics = per_layer_metrics(spec, cell, reduced, device["kind"],
                                    args.rehearse)
        busy = tracemod.busy_ns(reduced) / 1e9
        lo, hi = reduced["window"]
        device.update(busy_s=busy, window_s=(hi - lo) / 1e9)
    else:
        measured = {**cell.end_to_end(), "setup_s": setup_s}
        metrics = {m["name"]: {"value": measured[m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] in measured}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    attempted, failed = cell.attempts()
    result = {"correct": correct and failed < attempted,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": tracemod.top_ops(reduced),
                               "idle_gaps": tracemod.idle_gaps(reduced)}
    if args.rehearse:
        result["rehearse"] = True
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
