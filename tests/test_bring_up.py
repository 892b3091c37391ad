"""What running on the chip relies on, checked on the CPU.

* every entry point validates each executor option it takes and refuses
  an unknown executor (``"pallas"`` among them) with a ``ValueError``
  naming the option, and the CLI rejects the removed executor flags;
  with the backend reported as a TPU the default executors still run;
* ``benchmarks/run.py`` exits non-zero when a bench raises or writes an
  ``ERROR`` row, and ``table4`` refuses to spawn JAX children off the CPU;
* the compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR`` and
  otherwise picks one fixed, git-ignored directory;
* importing the entry points initialises no JAX backend (so the CLI's
  ``--tune-env`` re-exec happens before one exists);
* a degradation-ladder demotion logs a WARNING naming site, rung and cause.
"""

import logging
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.graphs.csr import build_csr
from repro.graphs.datasets import named_graph

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def on_tpu(monkeypatch):
    """Report a TPU backend to the program (the computation stays on the
    CPU), so no entry point can branch on the backend's name."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _graph():
    return build_csr(named_graph("karate_like"))


def _entry_points():
    from repro.core import (IncrementalTruss, compute_support, pkt,
                            pkt_dist, truss_pkt)
    from repro.serve import TrussEngine, TrussScheduler

    E = named_graph("karate_like")
    return {
        "pkt": lambda **kw: pkt(_graph(), **kw),
        "truss_pkt": lambda **kw: truss_pkt(E, **kw),
        "compute_support": lambda **kw: compute_support(_graph(), **kw),
        "pkt_dist": lambda **kw: pkt_dist(_graph(), **kw),
        "IncrementalTruss": lambda **kw: IncrementalTruss(E, **kw),
        "TrussEngine": lambda **kw: TrussEngine(**kw),
        "TrussScheduler": lambda **kw: TrussScheduler(start=False, **kw),
    }


#: every executor option each entry point takes
_EXECUTOR_AXES = {
    "pkt": ["mode", "table_mode"],
    "truss_pkt": ["mode", "table_mode"],
    "compute_support": ["table_mode"],
    "pkt_dist": ["table_mode"],
    "IncrementalTruss": ["mode", "table_mode", "hier_mode", "insert_mode"],
    "TrussEngine": ["mode", "table_mode", "hier_mode", "insert_mode"],
    "TrussScheduler": ["mode", "table_mode", "hier_mode", "insert_mode"],
}


@pytest.mark.parametrize("entry,axis", [
    (e, a) for e, axes in _EXECUTOR_AXES.items() for a in axes])
def test_entry_points_refuse_unknown_executor(entry, axis):
    with pytest.raises(ValueError, match=rf"^{axis} must be one of"):
        _entry_points()[entry](**{axis: "pallas"})


def test_tpu_default_executors_still_run(on_tpu):
    from repro.core import pkt, truss_numpy

    res = pkt(_graph())
    assert np.array_equal(res.trussness.astype(np.int64),
                          truss_numpy(_graph().El))


@pytest.mark.parametrize("flags", [["--mode", "pallas"],
                                   ["--support-mode", "jnp"]],
                         ids=["mode-pallas", "support-mode"])
def test_cli_rejects_removed_executor_flags(flags, monkeypatch):
    from repro.launch import truss as cli

    monkeypatch.setattr(cli, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as exc:
        cli.main(["--graph", "triangle", *flags])
    assert exc.value.code == 2


# ---- benchmarks/run.py ------------------------------------------------------

@pytest.fixture
def bench_run(monkeypatch):
    import benchmarks.run as run
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)
    return run


def test_bench_run_exits_nonzero_when_a_bench_raises(bench_run, monkeypatch):
    from benchmarks import fig6_levels

    def boom():
        raise RuntimeError("device lost")

    monkeypatch.setattr(fig6_levels, "run", boom)
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--only", "fig6"])
    assert exc.value.code == 1


def test_bench_run_counts_error_rows(bench_run):
    ok = lambda: iter(["a,1.0,x"])              # noqa: E731
    bad = lambda: iter(["b,ERROR,child died"])  # noqa: E731
    assert bench_run.run_benches({"a": ok, "b": bad}) == ["b"]
    assert bench_run.run_benches({"a": ok, "b": bad}, {"a"}) == []


def test_table4_refuses_off_cpu(monkeypatch):
    import jax

    from benchmarks import table4_parallel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU backend only"):
        table4_parallel.run(suite=("triangle",), device_counts=(1,))


# ---- compile cache ----------------------------------------------------------

def test_compile_cache_honours_env():
    from repro.compile_cache import CACHE_ENV, compile_cache_dir

    assert compile_cache_dir({CACHE_ENV: "/data/xla"}) == "/data/xla"


def test_compile_cache_default_is_fixed_and_ignored():
    from repro.compile_cache import compile_cache_dir

    first, second = compile_cache_dir({}), compile_cache_dir({})
    assert first == second == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_enable_compile_cache_keeps_the_env_dir(monkeypatch):
    import jax

    from repro.compile_cache import CACHE_ENV, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv(CACHE_ENV, "/data/xla")
    try:
        assert enable_compile_cache() == "/data/xla"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


# ---- process discipline -----------------------------------------------------

def test_importing_entry_points_initialises_no_backend():
    code = ("import repro.launch.truss, repro.serve, repro.core, "
            "benchmarks.run\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('OK')")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_ladder_demotion_logs_a_warning(caplog):
    from repro.serve.resilience import Ladder, RetryPolicy, run_with_resilience

    ladder = Ladder(("chunked", "host"), demote_after=1)
    calls = []

    def call(rungs):
        calls.append(rungs["flush"])
        if len(calls) == 1:
            raise RuntimeError("dispatch lost")
        return "done"

    with caplog.at_level(logging.WARNING, logger="repro.serve.resilience"):
        out = run_with_resilience(
            call, ladders={"flush": ladder}, primary="flush",
            policy=RetryPolicy(max_retries=1, base_delay_s=0.0,
                               max_delay_s=0.0))
    assert out == "done" and calls == ["chunked", "host"]
    (rec,) = [r for r in caplog.records if r.levelno == logging.WARNING]
    msg = rec.getMessage()
    assert "flush" in msg and "'host'" in msg and "dispatch lost" in msg
