"""Multi-device behaviour via subprocesses (jax locks the host device count
at first init, so these spawn fresh interpreters with forced device counts —
the main pytest process stays single-device)."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def run_script(body: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_dataframe_shard_map_equivalence():
    run_script("""
import numpy as np
from repro.data import wisconsin
from repro.engine.session import Session
from repro.core.frame import AFrame
from repro.launch.mesh import make_local_mesh

t = wisconsin.generate(10_000, seed=1)
raw = {k: np.asarray(v) for k, v in t.columns.items()}
mesh = make_local_mesh(data=8, model=1)
sess = Session(mesh=mesh, mode="shard_map")
sess.create_dataset("Data", t, dataverse="demo", indexes=["onePercent", "unique1"], primary="unique2")
df = AFrame("demo", "Data", session=sess)
assert len(df) == 10_000
n = len(df[(df["ten"] == 3) & (df["twentyPercent"] == 2) & (df["two"] == 1)])
assert n == int(((raw["ten"]==3)&(raw["twentyPercent"]==2)&(raw["two"]==1)).sum())
assert df["unique1"].max() == raw["unique1"].max()
g = df.groupby("oddOnePercent").agg("count")
assert g["count"].sum() == 10_000 and len(g["count"]) == 100
sh = df.sort_values("unique1", ascending=False).head(5)
assert list(sh["unique1"]) == sorted(raw["unique1"])[-5:][::-1]
n = len(df[(df["onePercent"] >= 10) & (df["onePercent"] <= 30)])
assert n == int(((raw["onePercent"]>=10)&(raw["onePercent"]<=30)).sum())
df2 = AFrame("demo", "Data", session=sess)
assert len(df.merge(df2, left_on="unique1", right_on="unique1")) == 10_000
print("OK")
""")


def test_dataframe_kernel_mode_sharded_equivalence():
    """mode="kernel" over an 8-shard mesh: each shard runs the relational
    kernels locally, partials merge with the minimal collectives."""
    run_script("""
import numpy as np
from repro.data import wisconsin
from repro.engine.session import Session
from repro.core.frame import AFrame
from repro.launch.mesh import make_local_mesh

t = wisconsin.generate(10_000, seed=1)
raw = {k: np.asarray(v) for k, v in t.columns.items()}
mesh = make_local_mesh(data=8, model=1)
sess = Session(mesh=mesh, mode="kernel")
sess.create_dataset("Data", t, dataverse="demo")
df = AFrame("demo", "Data", session=sess)
n = len(df[(df["ten"] == 3) & (df["twentyPercent"] == 3) & (df["two"] == 1)])
assert n == int(((raw["ten"]==3)&(raw["twentyPercent"]==3)&(raw["two"]==1)).sum()), n
g = df.groupby("oddOnePercent").agg("count")
assert g["count"].sum() == 10_000 and len(g["count"]) == 100
sh = df.sort_values("unique1", ascending=False).head(5)
assert list(sh["unique1"]) == sorted(raw["unique1"])[-5:][::-1]
n = len(df[(df["onePercent"] >= 10) & (df["onePercent"] <= 30)])
assert n == int(((raw["onePercent"]>=10)&(raw["onePercent"]<=30)).sum())
df2 = AFrame("demo", "Data", session=sess)
assert len(df.merge(df2, left_on="unique1", right_on="unique1")) == 10_000
from repro.kernels import ops
assert ops.DISPATCH_COUNTS.get("filter_count", 0) >= 1
assert ops.DISPATCH_COUNTS.get("segment_agg", 0) >= 1
assert ops.DISPATCH_COUNTS.get("topk", 0) >= 1
assert ops.DISPATCH_COUNTS.get("merge_join_count", 0) >= 1
print("OK")
""")


def test_hash_repartition_join():
    run_script("""
import numpy as np, jax.numpy as jnp
from repro.data import wisconsin
from repro.engine import distributed as D
from repro.engine.session import Session
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(data=8, model=1)
sess = Session(mesh=mesh, mode="shard_map")
t = wisconsin.generate(8_000, seed=2)
sess.create_dataset("Data", t, dataverse="d")
ds = sess.catalog.get("d", "Data")
k = ds.table.columns["unique1"]; m = ds.table.valid
total, drops = D.hash_repartition_counts(mesh, ("data",), k, m, k, m)
assert int(total) == 8_000 and int(drops) == 0, (int(total), int(drops))
# duplicate keys: ten has 800 of each value -> 800^2 * 10 pairs
k2 = ds.table.columns["ten"]
total2, drops2 = D.hash_repartition_counts(mesh, ("data",), k2, m, k2, m,
                                           capacity_factor=12.0)
want = sum(int((np.asarray(k2)==v).sum())**2 for v in range(10))
assert int(total2) == want, (int(total2), want)
print("OK")
""")


def test_train_step_dp_equivalence():
    """Same batch, 1 device vs 8-way DP mesh: identical loss."""
    run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh, MeshAxes
from repro.models import registry
from repro.models.optim import OptimConfig, init_opt_state
from repro.models.sharding import sharding_ctx, param_shardings
from repro.models.steps import init_train_state, make_train_step

cfg = get_config("qwen3-1.7b").reduced()
api = registry.get_api(cfg)
params, opt = init_train_state(jax.random.key(0), cfg, api)
batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)}
step = make_train_step(cfg, OptimConfig(total_steps=10), api)
_,_, m1 = jax.jit(step)(params, opt, batch)

mesh = make_local_mesh(data=4, model=2)
axes = MeshAxes.for_mesh(mesh)
shards = param_shardings(params, mesh, axes)
params_s = jax.device_put(params, shards)
opt_s = init_opt_state(params_s)
batch_s = {"tokens": jax.device_put(batch["tokens"], NamedSharding(mesh, P("data", None)))}
with sharding_ctx(mesh, axes):
    _,_, m2 = jax.jit(step)(params_s, opt_s, batch_s)
d = abs(float(m1["loss"]) - float(m2["loss"]))
assert d < 5e-3, (float(m1["loss"]), float(m2["loss"]))
print("OK", float(m1["loss"]), float(m2["loss"]))
""")


def test_moe_ep_shard_map_equivalence():
    """MoE layer: 1-device local dispatch == 4-way EP shard_map."""
    run_script("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_local_mesh, MeshAxes
from repro.models.config import ArchConfig, MoESpec
from repro.models.moe import init_moe, moe_ffn
from repro.models.sharding import sharding_ctx

cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=32, n_heads=2,
                 n_kv_heads=2, d_ff=64, vocab=64, d_head=16,
                 moe=MoESpec(num_experts=8, top_k=2, num_shared=1,
                             d_ff_expert=16, capacity_factor=16.0))
p = init_moe(jax.random.key(0), cfg, cfg.moe)
x = jax.random.normal(jax.random.key(1), (4, 8, 32), jnp.float32)
y1, aux1 = moe_ffn(x, p, cfg, cfg.moe)  # no ctx: local path
mesh = make_local_mesh(data=2, model=4)
with sharding_ctx(mesh, MeshAxes.for_mesh(mesh)):
    y2, aux2 = jax.jit(lambda x, p: moe_ffn(x, p, cfg, cfg.moe))(x, p)
np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
print("OK")
""", devices=8)


def test_elastic_checkpoint_reshard():
    """Save on a 4-shard layout, restore onto an 8-shard mesh."""
    run_script("""
import tempfile, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_local_mesh
from repro.runtime.checkpoint import CheckpointManager

with tempfile.TemporaryDirectory() as d:
    mesh4 = make_local_mesh(4, 1)
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh4, P("data", None)))
    cm = CheckpointManager(d, async_save=False)
    cm.save(1, {"w": w})
    mesh8 = make_local_mesh(8, 1)
    sh = {"w": NamedSharding(mesh8, P("data", None))}
    _, t = cm.restore(None, {"w": w}, shardings=sh)
    assert t["w"].sharding.mesh.shape["data"] == 8
    np.testing.assert_allclose(np.asarray(t["w"]), np.arange(64.0).reshape(8, 8))
print("OK")
""")


def test_shardmap_decode_matches_baseline():
    """§Perf C4: the explicit shard_map decode (rank-local 1-token cache
    write + psum online softmax) matches the GSPMD one-hot baseline."""
    run_script("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh, MeshAxes
from repro.models.registry import get_api
from repro.models.sharding import sharding_ctx

cfg0 = get_config("qwen3-1.7b").reduced()
api = get_api(cfg0)
params = api.init(jax.random.key(0), cfg0)
toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg0.vocab)
cache, _ = api.prefill(params, {"tokens": toks}, cfg0, 20)
new = jnp.ones((2, 1), jnp.int32)
c1, l1 = api.decode(params, cache, new, cfg0)
mesh = make_local_mesh(data=2, model=2)
cfg2 = dataclasses.replace(cfg0, decode_cache_update="shardmap")
with sharding_ctx(mesh, MeshAxes.for_mesh(mesh)):
    c2, l2 = jax.jit(lambda p, c, t: api.decode(p, c, t, cfg2))(params, cache, new)
assert float(jnp.max(jnp.abs(l1 - l2))) < 8e-2
assert (jnp.argmax(l1[:, -1], -1) == jnp.argmax(l2[:, -1], -1)).all()
np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]), atol=0.06)
print("OK")
""", devices=4)


def test_compressed_psum_shard_map():
    run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_local_mesh
from repro.runtime.compress import compressed_psum, init_error_state
from jax import shard_map

mesh = make_local_mesh(8, 1)
g_local = np.random.default_rng(0).normal(size=(8, 128)).astype(np.float32)

def f(g):
    err = init_error_state({"w": g})
    mean, _ = compressed_psum({"w": g}, err, "data")
    return mean["w"]

out = shard_map(f, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))(
    jnp.asarray(g_local))
want = g_local.mean(axis=0)
got = np.asarray(out)[0]
assert np.abs(got - want).max() < 0.02, np.abs(got - want).max()
print("OK")
""")
