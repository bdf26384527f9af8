"""Multi-device tests run in subprocesses with 8 fabricated CPU devices
(the main pytest process must keep the single real device — see conftest).
The children are pinned to the CPU (``JAX_PLATFORMS=cpu``): they test
meshes and checkpoint layouts on fabricated devices, not the chip."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidev(script: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


PREAMBLE = """
import jax, numpy as np, jax.numpy as jnp
from repro.utils.mesh import make_mesh_auto
mesh = make_mesh_auto((2, 4), ("data", "model"))
"""


def test_distributed_peel_matches_serial():
    run_multidev(PREAMBLE + """
from repro.graphs.generators import planted_dense
from repro.core import pbahmani_np
from repro.core.distributed import pbahmani_distributed
g, _, _ = planted_dense(700, 35, seed=5)
for eps in (0.0, 0.1):
    rd, md, pd = pbahmani_distributed(g, mesh, eps=eps)
    rs, ms, ps = pbahmani_np(g, eps=eps)
    assert abs(rd - rs) < 1e-4 and pd == ps, (rd, rs, pd, ps)
    assert np.array_equal(md, ms)
print("OK")
""")


def test_distributed_cbds_matches_serial():
    run_multidev(PREAMBLE + """
from repro.graphs.generators import erdos_renyi
from repro.core import cbds_np
from repro.core.distributed import cbds_distributed
g = erdos_renyi(500, 0.04, seed=3)
rd = cbds_distributed(g, mesh)
rs = cbds_np(g)
assert abs(rd["density"] - rs["density"]) < 1e-3, (rd["density"], rs["density"])
assert rd["k_star"] == rs["k_star"]
assert np.array_equal(rd["member_mask"], rs["member_mask"])
print("OK")
""")


def test_moe_ep_sharded_matches_dense():
    run_multidev(PREAMBLE + """
from repro.models.moe import MoEConfig, init_moe_params, moe_dense, moe_ep
cfg = MoEConfig(n_experts=8, top_k=2, d_model=16, d_ff=32, n_shared=1,
                capacity_factor=8.0)
p = jax.tree.map(lambda a: a[0], init_moe_params(jax.random.PRNGKey(0), cfg, 1))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
yd, auxd = moe_dense(x, p, cfg)
f = jax.jit(lambda x, p: moe_ep(x, p, cfg, mesh=mesh, dp=("data",), tp="model"))
ye, auxe = f(x, p)
np.testing.assert_allclose(np.asarray(yd), np.asarray(ye), rtol=3e-4, atol=3e-4)
# aux is computed per token-group and averaged (GShard semantics): close to
# but not identical with the global-batch aux of the dense oracle.
np.testing.assert_allclose(float(auxd), float(auxe), rtol=0.2)
print("OK")
""")


def test_moe_tp_sharded_matches_dense():
    run_multidev(PREAMBLE + """
from repro.models.moe import MoEConfig, init_moe_params, moe_dense
from repro.models.moe_tp import moe_tp
cfg = MoEConfig(n_experts=6, top_k=2, d_model=16, d_ff=32, capacity_factor=8.0)
p = jax.tree.map(lambda a: a[0], init_moe_params(jax.random.PRNGKey(0), cfg, 1))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
yd, _ = moe_dense(x, p, cfg)
f = jax.jit(lambda x, p: moe_tp(x, p, cfg, mesh=mesh, dp=("data",), tp="model"))
yt, _ = f(x, p)
np.testing.assert_allclose(np.asarray(yd), np.asarray(yt), rtol=3e-4, atol=3e-4)
print("OK")
""")


def test_sharded_train_step_matches_single_device():
    """pjit'd smoke train step on the 2x4 mesh == unsharded CPU step."""
    run_multidev(PREAMBLE + """
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.transformer import (TransformerConfig, init_params, loss_fn,
                                      param_specs)
from repro.models.layers import ShardCtx
from repro.optim import adamw
cfg = TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                        n_kv_heads=2, d_ff=64, vocab=64)
p = init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
# single device reference
loss_ref = loss_fn(p, toks, toks, cfg)
# sharded
specs = param_specs(cfg, mesh)
ctx = ShardCtx(mesh=mesh, dp=("data",), sp=True)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                  is_leaf=lambda x: isinstance(x, P))
p_sh = jax.tree.map(lambda a, s: jax.device_put(a, s), p, sh)
toks_sh = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
loss_sh = jax.jit(lambda p, t: loss_fn(p, t, t, cfg, ctx, mesh))(p_sh, toks_sh)
np.testing.assert_allclose(float(loss_ref), float(loss_sh), rtol=2e-4)
print("OK")
""")


def test_compressed_psum():
    run_multidev("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim import compressed_psum
from repro.utils.mesh import make_mesh_auto
mesh = make_mesh_auto((8,), ("d",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32))

def body(xl):
    return compressed_psum(xl[0], "d")

out = jax.shard_map(body, mesh=mesh, in_specs=(P("d", None, None),),
                    out_specs=P(), check_vma=False)(x)
exact = np.asarray(x).sum(axis=0)
rel = np.abs(np.asarray(out) - exact).max() / np.abs(exact).max()
assert rel < 0.02, rel   # int8 quantization error bound
print("OK", rel)
""")


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint on a 2x4 mesh, restore onto 1x8 and single device."""
    script_save = PREAMBLE + f"""
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                   NamedSharding(mesh, P("data", "model")))
mgr = CheckpointManager({str(tmp_path)!r})
mgr.save(1, {{"w": w}}, blocking=True)
print("saved")
"""
    run_multidev(script_save)
    script_load = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.launch.train import restore_elastic
from repro.utils.mesh import make_mesh_auto
mesh = make_mesh_auto((8,), ("data",))
mgr = CheckpointManager(%r)
step, st = restore_elastic(
    mgr, {"w": np.zeros((8, 8))},
    {"w": NamedSharding(mesh, P("data", None))})
assert step == 1
np.testing.assert_array_equal(np.asarray(st["w"]), np.arange(64.0).reshape(8, 8))
print("OK")
""" % str(tmp_path)
    run_multidev(script_load)
    # and onto the single real device
    script_1dev = """
import numpy as np
from repro.checkpoint import CheckpointManager
mgr = CheckpointManager(%r)
step, st = mgr.restore({"w": np.zeros((8, 8))})
np.testing.assert_array_equal(st["w"], np.arange(64.0).reshape(8, 8))
print("OK")
""" % str(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script_1dev], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr


def test_vp_segment_sum_matches_reference():
    """Vertex-partitioned aggregation (EXPERIMENTS §Perf #2) == oracle."""
    run_multidev("""
import jax, jax.numpy as jnp, numpy as np
from repro.utils.mesh import make_mesh_auto
from repro.kernels import ops as kops
from repro.kernels.ref import segment_sum_ref
from repro.graphs.generators import erdos_renyi
from repro.graphs.partition import partition_by_dst_block

mesh = make_mesh_auto((4, 2), ("data", "model"))
n = 512
g = erdos_renyi(n, 0.05, seed=3)
src, dst, _ = partition_by_dst_block(g, 4)
bounds = np.searchsorted(dst, np.arange(0, n + 1, n // 4))
per = int(np.ceil(max(np.diff(bounds)) / 2) * 2)
E = per * 4
src_p = np.full(E, n, np.int32); dst_p = np.full(E, n, np.int32)
for b in range(4):
    lo, hi = bounds[b], bounds[b + 1]
    src_p[b*per:b*per+(hi-lo)] = src[lo:hi]
    dst_p[b*per:b*per+(hi-lo)] = dst[lo:hi]
rng = np.random.default_rng(0)
h = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))
vals = jnp.where((jnp.asarray(src_p) < n)[:, None],
                 jnp.take(h, jnp.minimum(jnp.asarray(src_p), n - 1), axis=0), 0.0)

@jax.jit
def run(vals, ids):
    with kops.segment_output_sharding(mesh, ("data",), min_segments=1):
        return kops.vp_segment_sum(vals, ids, n)

out = run(vals, jnp.asarray(dst_p))
exp = segment_sum_ref(vals, jnp.asarray(dst_p), n)
np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5)
g_ = jax.grad(lambda v: run(v, jnp.asarray(dst_p)).sum())(vals)
assert bool(jnp.all(jnp.isfinite(g_)))
print("OK")
""")
