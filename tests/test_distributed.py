"""Multi-device behaviour via subprocesses (host-platform device count must
be set before jax initializes, so each case runs in its own interpreter)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 8 virtual devices on a <4-core host makes XLA's spin-waiting CPU
# collectives pathological (minutes instead of seconds); scale the virtual
# fleet to the machine while keeping it genuinely multi-device.
DEVICES = 8 if (os.cpu_count() or 1) >= 4 else 4


def run_py(code: str, devices: int = DEVICES, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # Pin the subprocess to the CPU platform: the device-count flag only
    # multiplies *host* devices, and a child that probed for the TPU would
    # contend with the parent for it (one process per chip).  On a TPU
    # machine the distributed path runs in one process instead:
    # ``python chip_smoke.py --four-chips``.
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_pkt_dist_matches_single_device():
    out = run_py("""
import numpy as np, jax
from repro.graphs.csr import build_csr, edges_from_arrays
from repro.core import truss_numpy, pkt_dist
rng = np.random.default_rng(5)
n = 50
mask = rng.random((n, n)) < 0.25
src, dst = np.nonzero(np.triu(mask, 1))
g = build_csr(edges_from_arrays(src, dst, n))
assert len(jax.devices()) >= 2
t = pkt_dist(g, chunk=64)
assert np.array_equal(t, truss_numpy(g.El))
print("OK", g.m)
""")
    assert "OK" in out


def test_pkt_dist_table_modes():
    """Each device resolves its own slice of the peel table (``device``),
    or takes an even share of the host-resolved rows (``numpy``): both
    peel to the oracle bitwise, a triangle-free graph included."""
    out = run_py("""
import numpy as np, jax
from repro.graphs.csr import build_csr, edges_from_arrays
from repro.core import truss_numpy, pkt_dist
rng = np.random.default_rng(7)
n = 40
mask = rng.random((n, n)) < 0.3
src, dst = np.nonzero(np.triu(mask, 1))
star = np.stack([np.zeros(9, np.int64), np.arange(1, 10)], axis=1)
assert len(jax.devices()) >= 2
for E in (edges_from_arrays(src, dst, n), star):
    g = build_csr(E)
    for tm in ("device", "numpy"):
        for chunk in (8, 64):
            t = pkt_dist(g, chunk=chunk, table_mode=tm)
            assert np.array_equal(t, truss_numpy(g.El)), (tm, chunk)
print("OK", g.m)
""")
    assert "OK" in out


def test_train_step_sharded_small_mesh():
    """Real sharded execution (2x4 mesh): two steps run and loss is finite,
    and the sharded result matches single-device execution."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp, jax.random as jr, dataclasses, functools
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.configs import reduced_config
from repro.models.model import init_params
from repro.models import sharding as shard_rules
from repro.train.step import TrainState, train_step
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.data.pipeline import SyntheticTokens

cfg = dataclasses.replace(reduced_config("smollm_135m"),
                          compute_dtype="float32", d_model=64, n_heads=4,
                          n_kv_heads=4, head_dim=16)
mesh = make_host_mesh(n_data=2)   # (data=2, model=4)
params = init_params(cfg, jr.PRNGKey(0))
state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                   opt=adamw_init(params))
opt_cfg = AdamWConfig(lr=1e-3)
src = SyntheticTokens(cfg.vocab, 32, 4, seed=3)
batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}

# single-device reference
ref, m_ref = train_step(state, batch, cfg, opt_cfg)

pspec = shard_rules.param_specs(cfg, jax.eval_shape(lambda: params),
                                mesh.axis_names)
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                   is_leaf=lambda x: isinstance(x, P))
state_sh = TrainState(step=NamedSharding(mesh, P()), params=psh,
                      opt={"m": psh, "v": psh})
bsh = {k: NamedSharding(mesh, P("data")) for k in batch}
jfn = jax.jit(functools.partial(train_step, cfg=cfg, opt_cfg=opt_cfg),
              in_shardings=(state_sh, bsh), out_shardings=(state_sh, None))
with mesh:
    st = jax.device_put(state, state_sh)
    b = jax.device_put(batch, bsh)
    st, m = jfn(st, b)
assert np.isfinite(float(m["ce"]))
assert abs(float(m["ce"]) - float(m_ref["ce"])) < 1e-3, (float(m["ce"]), float(m_ref["ce"]))
err = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), ref.params, st.params)))
assert err < 1e-4, err
print("OK sharded-vs-single err", err)
""")
    assert "OK" in out


def test_dryrun_cells_on_tiny_mesh():
    """The dry-run builder compiles decode + prefill + train for a reduced
    arch on an 8-device (2x4) mesh — the same code path as the 512-chip run."""
    out = run_py("""
import numpy as np, jax, dataclasses
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
import repro.configs as C
import repro.launch.dryrun as DR

# dryrun.py forces a 512-virtual-device host platform at import, so the
# (2, 4) mesh is always satisfiable here regardless of run_py's device count
mesh = make_mesh((2, 4), ("data", "model"))
# shrink the shape table so reduced configs fit fast
C.SHAPES["train_4k"] = (64, 8, "train")
C.SHAPES["prefill_32k"] = (128, 4, "prefill")
C.SHAPES["decode_32k"] = (128, 8, "decode")
for arch in ("qwen3_8b", "phi35_moe_42b", "zamba2_7b"):
    cfg = reduced_config(arch)
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        rec = DR.lower_cell(cfg, shape, mesh)
        assert rec["flops"] > 0, (arch, shape)
        print("ok", arch, shape, rec["collectives"]["total_bytes"] > 0)
print("OK")
""")
    assert "OK" in out


def test_support_dist_equals_local():
    out = run_py("""
import numpy as np, jax
from repro.graphs.csr import build_csr, edges_from_arrays
from repro.core import compute_support
from repro.core.pkt_dist import pkt_dist
from repro.core import truss_pkt
rng = np.random.default_rng(9)
n = 64
mask = rng.random((n, n)) < 0.2
src, dst = np.nonzero(np.triu(mask, 1))
E = edges_from_arrays(src, dst, n)
g = build_csr(E)
t_local = truss_pkt(E, reorder=False)
t_dist = pkt_dist(g, chunk=32)
key = g.El[:,0].astype(np.int64) * n + g.El[:,1]
kin = E[:,0] * n + E[:,1]
pos = np.searchsorted(key, kin)
assert np.array_equal(t_dist[pos], t_local)
print("OK")
""")
    assert "OK" in out


def test_checkpoint_elastic_reshard():
    """Save on a (1,1) layout, restore onto a (2,4) mesh — elastic rescale."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp, jax.random as jr, dataclasses, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.configs import reduced_config
from repro.models.model import init_params
from repro.models import sharding as shard_rules
from repro.checkpoint import save_checkpoint, restore_checkpoint

cfg = dataclasses.replace(reduced_config("qwen3_8b"), compute_dtype="float32")
params = init_params(cfg, jr.PRNGKey(0))
d = tempfile.mkdtemp()
save_checkpoint(d, 7, params)           # single-device layout

mesh = make_host_mesh(n_data=2)          # (2, 4) — a different fleet shape
pspec = shard_rules.param_specs(cfg, jax.eval_shape(lambda: params),
                                mesh.axis_names)
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                   is_leaf=lambda x: isinstance(x, P))
step, restored = restore_checkpoint(d, jax.eval_shape(lambda: params),
                                    shardings=psh)
assert step == 7
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# restored leaves actually live on the new mesh
leaf = jax.tree.leaves(restored)[0]
assert len(leaf.sharding.device_set) >= 1
print("OK elastic reshard")
""")
    assert "OK" in out
