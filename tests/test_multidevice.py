"""Multi-device semantics via subprocesses with 8 forced host devices
(conftest must NOT set XLA_FLAGS globally — these tests isolate it)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_snippet(code: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    print(run_snippet(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.distributed import ShardCtx
from repro.models import build
from repro.training import init_state, make_train_step, opt_config_for, state_shardings

cfg = get_config("llama3-8b").reduced()
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)

# single-device reference
m1 = build(cfg, ShardCtx.single())
o1 = opt_config_for(cfg, lr=1e-3)
p1, s1 = init_state(m1, o1, jax.random.key(0))
p1b, _, met1 = jax.jit(make_train_step(m1, o1))(p1, s1, {"tokens": tokens})

# sharded
ctx = ShardCtx.for_mesh(mesh, "train")
m2 = build(cfg, ctx)
p2, s2 = init_state(m2, o1, jax.random.key(0))
psh, osh = state_shardings(m2, o1, ctx, p2, s2)
p2 = jax.device_put(p2, psh); s2 = jax.device_put(s2, osh)
with mesh:
    p2b, _, met2 = jax.jit(make_train_step(m2, o1))(p2, s2, {"tokens": tokens})
d = abs(float(met1["loss"]) - float(met2["loss"]))
assert d < 5e-3, d
err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
          zip(jax.tree.leaves(p1b), jax.tree.leaves(p2b)))
assert err < 5e-2, err
print("SHARDED TRAIN OK", d, err)
"""))


def test_shard_map_decode_matches_local():
    print(run_snippet(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed import ShardCtx
from repro.models.attention import decode_attention_local, decode_attention_sharded, cache_update_sharded

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardCtx.for_mesh(mesh, "decode")
rng = np.random.default_rng(0)
B, S, Hq, Hkv, D = 4, 64, 8, 2, 16
q = jnp.asarray(rng.normal(size=(B,1,Hq,D)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(B,S,Hkv,D)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(B,S,Hkv,D)), jnp.float32)
vl = jnp.asarray([3, 17, 42, 64], jnp.int32)
kc_s = jax.device_put(kc, NamedSharding(mesh, P("data", "model")))
vc_s = jax.device_put(vc, NamedSharding(mesh, P("data", "model")))
with mesh:
    out = jax.jit(lambda q,k,v,l: decode_attention_sharded(q,k,v,l,ctx))(q, kc_s, vc_s, vl)
ref = decode_attention_local(q, kc, vc, vl)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-4, err

# predicated cache update across seq shards
kn = jnp.asarray(rng.normal(size=(B,1,Hkv,D)), jnp.float32)
vn = jnp.asarray(rng.normal(size=(B,1,Hkv,D)), jnp.float32)
pos = jnp.asarray([0, 17, 42, 63], jnp.int32)
with mesh:
    kc2, vc2 = jax.jit(lambda a,b,c,d,p: cache_update_sharded(a,b,c,d,p,ctx))(kc_s, vc_s, kn, vn, pos)
ref_ctx = ShardCtx.single(kind="decode")
kc2r, vc2r = cache_update_sharded(kc, vc, kn, vn, pos, ref_ctx)
err2 = float(jnp.max(jnp.abs(kc2 - kc2r)))
assert err2 < 1e-6, err2
print("SHARD_MAP DECODE OK", err, err2)
"""))


def test_elastic_checkpoint_restore_across_meshes():
    print(run_snippet(r"""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager

# save sharded over 8 devices as (8,), restore onto a (2,4) mesh sharding
mesh8 = jax.make_mesh((8,), ("data",))
mesh24 = jax.make_mesh((2, 4), ("data", "model"))
w = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
w8 = jax.device_put(w, NamedSharding(mesh8, P("data", None)))
with tempfile.TemporaryDirectory() as d:
    cm = CheckpointManager(d)
    cm.save(1, {"w": w8})
    tpl = {"w": jax.ShapeDtypeStruct(w.shape, w.dtype)}
    sh = {"w": NamedSharding(mesh24, P("model", "data"))}
    back = cm.restore(1, tpl, shardings=sh)
    assert back["w"].sharding == sh["w"]
    assert bool(jnp.all(back["w"] == w))
print("ELASTIC RESTORE OK")
"""))


def test_cluster_submesh_isolation():
    print(run_snippet(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.clusters import ClusterManager
from repro.core.persistent import PersistentRuntime
from repro.core import mailbox as mb
from jax.sharding import NamedSharding, PartitionSpec as P

cm = ClusterManager(n_clusters=2, axis_names=("data",))
assert cm.check_disjoint() and len(cm.clusters) == 2
assert all(c.n_devices == 4 for c in cm.clusters)

def work(state, desc):
    state = dict(state)
    state["x"] = state["x"] + jax.lax.psum(state["x"] * 0 + 1.0, "data")
    return state, state["x"].sum()[None]

outs = []
for c in cm.clusters:
    sh = NamedSharding(c.mesh, P("data"))
    def fn(state, desc):
        state = dict(state); state["x"] = state["x"] + 1.0
        return state, state["x"].sum()[None]
    rt = PersistentRuntime([("w", fn)], result_template=jnp.zeros((1,), jnp.float32),
                           mesh=c.mesh, state_shardings={"x": sh})
    rt.boot({"x": jnp.zeros((8,), jnp.float32)})
    res, _ = rt.run_sync(mb.WorkDescriptor(opcode=0))
    outs.append(float(res[0]))
    # the cluster's state lives ONLY on its own devices (spatial isolation)
    devset = {d.id for d in np.asarray(rt.state["x"].sharding.device_set if hasattr(rt.state["x"].sharding, "device_set") else [], dtype=object).tolist()} if False else {d.id for d in rt.state["x"].sharding.device_set}
    assert devset == {d.id for d in c.devices.tolist()}, (devset, c.cid)
    rt.dispose()
assert outs == [8.0, 8.0]
print("CLUSTER ISOLATION OK")
"""))


def test_lk_system_clusters_on_their_own_devices():
    """LkSystem with one cluster per device, under both runtimes: every
    cluster's state sits on its own device, executables are compiled per
    device, and every cluster's results equal a one-cluster run (the
    four-chip phase of chip_smoke.py, on forced host devices)."""
    out = run_snippet(r"""
import sys
sys.path.insert(0, %r)
import jax
import chip_smoke
placed = chip_smoke.clusters_phase(jax.devices()[:4])
assert placed["scan"] == placed["mega"] == [f"cpu:{i}" for i in range(4)], placed
print("CLUSTERS OWN DEVICES OK", placed)
""" % REPO)
    assert "CLUSTERS OWN DEVICES OK" in out
