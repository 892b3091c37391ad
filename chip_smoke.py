#!/usr/bin/env python3
"""Smoke test of the truss system's main path on a TPU, at full size.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # pkt_dist over four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]

One chip (the default) runs two phases in this one process, both on
``rmat-medium`` (Graph500-style R-MAT, scale 15, edge factor 8;
m = 234,101, both wedge tables padded to 2^25 rows):

* one-shot decomposition: the graph is degeneracy-ordered and decomposed
  by ``pkt`` on the default executors, once cold and once warm; its
  trussness must equal the host Wang–Cheng reference (``core/wc.py``)
  bitwise and the warm run must compile nothing;
* serving: the graph is opened through a ``TrussScheduler``, 200 requests
  of the 90/9/1 query/update/open mix are replayed through it at 5 offered
  qps, then one ``communities_async(h, 4)`` is answered; every result must
  equal a synchronous ``TrussEngine`` replay bitwise, with no failed
  request, no retry and no degradation-ladder demotion.

``rmat-medium`` is the largest R-MAT graph whose phases fit the run's
1200 s limit on one v5e: a warm ``pkt`` of ``rmat-large`` (scale 17)
takes about 1000 s there.  The offered rate stays under what one
``rmat-medium`` handle sustains (about 7 qps), so requests do not queue
without bound.

``--four-chips`` runs only the distributed path: ``pkt_dist`` on
``rmat-medium`` over a 4-device mesh, checked bitwise against Wang–Cheng,
with the per-device peak memory showing the wedge tables split across
devices.

Without a TPU the script exits non-zero and prints no result.
``--rehearse`` shrinks the graphs and accepts any platform, naming the
one it found.  The last line of stdout is one JSON object, printed only
when every check passed:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import logging
import pathlib
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: (graph of every phase, serving requests, offered qps)
FULL = ("rmat-medium", 200, 5.0)
REHEARSE = ("rmat-tiny", 60, 100.0)


class CompileCounter:
    """Counts programs compiled or loaded, and cache loads, via jax.monitoring.

    JAX reports a backend-compile duration for every program it obtains,
    whether the XLA compiler built it or the persistent cache supplied it;
    cache loads are counted separately.
    """

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int, float]:
        """(programs obtained, cache loads, their seconds) so far."""
        return self.compiles, self.cache_hits, self.compile_s


def log(msg: str) -> None:
    """One progress line on stdout (never the last one)."""
    print(msg, flush=True)


def peak_bytes(device) -> int | None:
    """``peak_bytes_in_use`` of ``device``, where the backend reports it."""
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def load_graph(name: str):
    """``named_graph`` → degeneracy order → relabel → CSR, as the CLI does."""
    from repro.graphs.csr import build_csr, degeneracy_order, relabel
    from repro.graphs.datasets import named_graph

    t0 = time.perf_counter()
    E = named_graph(name)
    n = int(E.max()) + 1
    g = build_csr(relabel(E, degeneracy_order(E, n)), n)
    log(f"{name}: n={g.n} m={g.m} host prep {time.perf_counter() - t0:.3f}s")
    return g


def reference(g):
    """The host Wang–Cheng trussness of ``g`` (numpy only), timed."""
    from repro.core import truss_wc

    t0 = time.perf_counter()
    ref = truss_wc(g)
    log(f"truss_wc reference: {time.perf_counter() - t0:.3f}s "
        f"t_max={int(ref.max(initial=2))}")
    return ref


def one_shot(name: str, counter: CompileCounter, device) -> list[str]:
    """Cold + warm ``pkt`` on ``name`` vs Wang–Cheng; returns failures."""
    import numpy as np

    from repro.core import pkt

    g = load_graph(name)
    fails = []
    runs = {}
    for label in ("cold", "warm"):
        c0, h0, s0 = counter.snapshot()
        t0 = time.perf_counter()
        res = pkt(g, phase_timings=True)   # returns host arrays: synced
        dt = time.perf_counter() - t0
        c1, h1, s1 = counter.snapshot()
        runs[label] = res
        phases = " ".join(f"{k}={v:.3f}s" for k, v in sorted(res.phases.items()))
        log(f"pkt {label}: {dt:.3f}s levels={res.levels} "
            f"sublevels={res.sublevels} compactions={res.compactions} "
            f"programs={c1 - c0} cache_loads={h1 - h0} "
            f"compile_s={s1 - s0:.3f} phases: {phases}")
        if label == "warm" and (c1 - c0 or h1 - h0):
            fails.append(f"warm pkt compiled {c1 - c0} programs "
                         f"(+{h1 - h0} cache loads)")
    log(f"peak_bytes_in_use after pkt: {peak_bytes(device)}")
    cold, warm = runs["cold"].trussness, runs["warm"].trussness
    ref = reference(g)
    log(f"m={g.m} t_max={int(cold.max(initial=2))}")
    if not np.array_equal(cold.astype(np.int64), ref):
        fails.append("cold pkt trussness differs from truss_wc")
    if not np.array_equal(warm, cold):
        fails.append("warm pkt trussness differs from the cold run")
    else:
        log("pkt trussness == truss_wc (bitwise): OK")
    return fails


def serving(name: str, requests: int, qps: float) -> list[str]:
    """Paced 90/9/1 replay + one community query vs a sync replay."""
    import numpy as np

    from repro.graphs.datasets import named_graph
    from repro.launch.truss import (latency_percentiles, replay_schedule,
                                    serve_schedule, verify_replay)
    from repro.serve import TrussScheduler

    E = named_graph(name)
    n = int(E.max()) + 1
    fails = []
    sched = TrussScheduler(max_queue=max(256, 4 * requests),
                           max_inflight=max(64, 4 * requests))
    try:
        t0 = time.perf_counter()
        h = sched.open_async(E).result()
        log(f"serve {name}: m={h.m} open {time.perf_counter() - t0:.3f}s "
            f"requests={requests} qps={qps} mix=90/9/1 query/update/open")
        ops = serve_schedule(E, n, requests, seed=0)
        outcomes, lat, dt = replay_schedule(sched, h, ops, qps)
        t0 = time.perf_counter()
        comms = sched.communities_async(h, 4).result()
        log(f"communities_async(h, 4): {len(comms)} communities in "
            f"{time.perf_counter() - t0:.3f}s")
        st = sched.stats()
    finally:
        sched.close()
    for kind, p in latency_percentiles(lat).items():
        log(f"serve {kind:6s} n={p['n']} p50={p['p50_ms']:.3f}ms "
            f"p99={p['p99_ms']:.3f}ms max={p['max_ms']:.3f}ms")
    failed = [e for s, e in outcomes if s != "ok"]
    ladders = st["resilience"]
    demotions = sum(r["demotions"] for r in ladders.values())
    off_rung = [s for s, r in ladders.items() if r["rung"] != r["rungs"][0]]
    log(f"serve achieved {len(ops) / dt:.1f} qps; failed={len(failed)} "
        f"retries={st['counters']['retries']} demotions={demotions} "
        f"errors={st['counters']['errors']} "
        f"rungs={ {s: r['rung'] for s, r in ladders.items()} }")
    if failed:
        fails.append(f"{len(failed)} requests failed, first: {failed[0]!r}")
    if st["counters"]["retries"] or demotions or off_rung:
        fails.append(f"resilience fallback used: retries="
                     f"{st['counters']['retries']} demotions={demotions} "
                     f"off first rung={off_rung}")
    ok, hs = verify_replay(E, ops, outcomes, h)
    sync = hs.communities(4)
    ok = ok and len(sync) == len(comms) and all(
        np.array_equal(a, b) for a, b in zip(comms, sync))
    log(f"serve async == sync replay incl. communities (bitwise): "
        f"{'OK' if ok else 'MISMATCH'}")
    if not ok:
        fails.append("async serving results differ from the sync replay")
    return fails


def four_chips(name: str) -> list[str]:
    """``pkt_dist`` over a 4-device mesh vs Wang–Cheng + memory split."""
    import jax
    import numpy as np

    from repro.core import pkt_dist

    devices = jax.devices()[:4]
    if len(devices) < 4:
        return [f"--four-chips needs 4 devices, found {len(devices)}"]
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    g = load_graph(name)
    fails = []
    # the host reference overlaps the cold run (four chips bill by the
    # second); the warm run is timed alone
    ref = {}
    wc = threading.Thread(target=lambda: ref.setdefault("t", reference(g)))
    wc.start()
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        t = pkt_dist(g, mesh=mesh)
        log(f"pkt_dist {label} over {len(devices)} devices: "
            f"{time.perf_counter() - t0:.3f}s"
            + (" (host reference running alongside)" if wc.is_alive()
               else ""))
        wc.join()
    peaks = [peak_bytes(d) for d in devices]
    log(f"per-device peak_bytes_in_use: {peaks}")
    if None not in peaks:
        # tables built on one device would put ~4x the bytes there
        if max(peaks) > 1.5 * min(peaks):
            fails.append(f"device memory not split evenly: {peaks}")
    elif devices[0].platform == "tpu":
        fails.append("the TPU reported no peak_bytes_in_use")
    if not np.array_equal(t, ref["t"]):
        fails.append("pkt_dist trussness differs from truss_wc")
    else:
        log("pkt_dist trussness == truss_wc (bitwise): OK")
    return fails


def main(argv=None) -> int:
    """Run the selected phases; return the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only pkt_dist over a 4-device mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny graphs on whatever platform JAX finds")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stdout,
                        format="%(levelname)s %(name)s: %(message)s")

    import jax

    from repro.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"use --rehearse for a tiny run off the chip", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    graph, requests, qps = REHEARSE if args.rehearse else FULL

    if args.four_chips:
        fails = four_chips(graph)
    else:
        fails = one_shot(graph, counter, dev)
        fails += serving(graph, requests, qps)
    if fails:
        for f in fails:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    out = {"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())}}
    if args.rehearse:
        out["rehearse"] = True
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
