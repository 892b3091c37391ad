"""Paper Table 4 + Fig. 5: parallel scaling of the distributed PKT.

XLA host devices are the stand-in for cores: each device count runs in a
subprocess (device count locks at jax init). The measured quantity is the
full decomposition wall time of `pkt_dist` (table-sharded, psum-combined),
mirroring the paper's 1→24-core relative-speedup figure.

CPU only: on an accelerator the parent process already holds the device,
so a child that needs it would fail or hang; ``run`` refuses there.
"""

from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import row

_CHILD = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
import numpy as np, jax
from repro.graphs.datasets import named_graph
from repro.graphs.csr import build_csr, relabel, degeneracy_order
from repro.core.pkt_dist import pkt_dist
name = sys.argv[2]
E = named_graph(name)
n = int(E.max()) + 1
E = relabel(E, degeneracy_order(E, n))
g = build_csr(E, n)
t = pkt_dist(g, chunk=1 << 12)            # warmup+compile
t0 = time.perf_counter()
t = pkt_dist(g, chunk=1 << 12)
dt = time.perf_counter() - t0
print(f"RESULT {dt:.4f} {g.wedge_count()}")
"""


def run(suite=("rmat-small", "ba-small", "er-small"),
        device_counts=(1, 2, 4, 8)) -> list[str]:
    """CSV rows: serial-vs-vmapped scaling proxy (paper Table 4).

    Raises:
        RuntimeError: the parent's JAX backend is not the CPU.
    """
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"table4 starts one JAX child process per device count, and "
            f"this process already holds the {jax.default_backend()} "
            f"device; it runs on the CPU backend only")
    out = []
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    for name in suite:
        base = None
        for d in device_counts:
            p = subprocess.run(
                [sys.executable, "-c", _CHILD, str(d), name],
                capture_output=True, text=True, env=env, timeout=900)
            if p.returncode != 0:
                out.append(f"table4/{name}/p{d},ERROR,{p.stderr[-120:]}")
                continue
            line = [l for l in p.stdout.splitlines()
                    if l.startswith("RESULT")][0]
            dt, wedges = float(line.split()[1]), int(line.split()[2])
            base = base or dt
            out.append(row(
                f"table4/{name}/p{d}", dt,
                f"speedup={base / dt:.2f};GWeps={wedges / dt / 1e9:.4f}"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
