"""Distribution layer: sharding rules, EP MoE equivalence, multipath
wakeup lowering — on an 8-virtual-device mesh in subprocesses (device
count must not leak into this process; see dryrun.py note)."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run8(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, cwd=REPO, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_sharding_rules_divisibility():
    """Rules respect divisibility: yi's 56 heads stay unsharded on a
    16-way axis while the flat projections shard; mamba2's 50280 vocab
    embedding replicates."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed.sharding import param_pspec
    from repro.models.init import abstract_params

    mesh = jax.make_mesh((1, 1), ("data", "model"))  # sizes faked below

    class FakeMesh:
        axis_names = ("data", "model")
        devices = type("D", (), {"shape": (16, 16)})()

    cfg = get_config("yi-34b")
    params = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {"/".join(str(p) for p in path): param_pspec(path, leaf, FakeMesh())
             for path, leaf in flat}
    wq = [v for k, v in specs.items() if k.endswith("['wq']")][0]
    assert wq == P(None, None, "model")     # flat H*Dh = 7168 divides 16
    emb = specs["['embedding']"]
    assert emb == P("model", None)          # 64000 divides 16

    cfg2 = get_config("mamba2-370m")
    params2 = abstract_params(cfg2)
    flat2 = jax.tree_util.tree_flatten_with_path(params2)[0]
    emb2 = [param_pspec(p, l, FakeMesh()) for p, l in flat2
            if str(p[-1].key) == "embedding"][0]
    assert emb2 == P(None, None)            # 50280 % 16 != 0 -> replicated


def test_shard_binds_host_mesh_axes():
    """Under ``jax.set_mesh(make_host_mesh())`` ``shard()`` sees the mesh
    and constrains with its axes; a quiet fall-through to "no mesh" would
    leave the constraint out of the lowered program."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    from repro.models.layers import BATCH, MODEL, mesh_axis_sizes, pspec, shard

    mesh = make_host_mesh()
    n = len(jax.devices())
    assert pspec(BATCH, MODEL) == P(None, None)     # no mesh: no axes
    with jax.set_mesh(mesh):
        assert mesh_axis_sizes() == {"data": n, "model": 1}
        assert pspec(BATCH, MODEL) == P("data", "model")
        f = jax.jit(lambda x: shard(x * 2, BATCH, MODEL))
        x = jnp.ones((4 * n, 8))
        text = f.lower(x).as_text()
        out = f(x)
    constraints = [l for l in text.splitlines() if "sharding_constraint" in l]
    assert len(constraints) == 1, text
    assert '[{"data"}, {"model"}]' in constraints[0]
    assert out.sharding.mesh.axis_names == ("data", "model")
    assert mesh_axis_sizes() == {}          # no mesh outside the context


def test_train_step_on_8dev_mesh_subprocess():
    """A reduced model train step lowers, compiles and RUNS sharded on a
    (2 data x 4 model) mesh; loss finite."""
    code = r"""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import batch_shardings, params_shardings
from repro.models import init_params
from repro.training import AdamWConfig, TrainConfig, make_train_step, init_adamw

cfg = dataclasses.replace(
    get_config("olmoe-1b-7b").reduced(), dtype=jnp.float32,
    n_experts=4, top_k=2, moe_ep=True,
)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model=4)
params = init_params(jax.random.PRNGKey(0), cfg)
opt = init_adamw(params)
step = make_train_step(cfg, TrainConfig(remat=True, opt=AdamWConfig()))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
batch = {"tokens": toks, "labels": toks}
with jax.set_mesh(mesh):
    p_sh = params_shardings(params, mesh)
    b_sh = batch_shardings(batch, mesh)
    o_sh = type(opt)(step=None, mu=params_shardings(opt.mu, mesh),
                     nu=params_shardings(opt.nu, mesh))
    jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None))
    compiled = jitted.lower(params, opt, batch).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo, "EP MoE must emit all-to-all"
    new_p, new_o, metrics = jitted(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"])), metrics
print("MESH_TRAIN_OK", float(metrics["loss"]))
"""
    out = run8(code)
    assert "MESH_TRAIN_OK" in out


def test_ep_moe_matches_reference_subprocess():
    code = r"""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.moe import moe_ffn
from repro.models.moe_ep import moe_ffn_ep
cfg = dataclasses.replace(get_config("olmoe-1b-7b").reduced(),
                          n_experts=8, top_k=2, capacity_factor=64.0,
                          dtype=jnp.float32)
d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
ks = jax.random.split(jax.random.PRNGKey(0), 5)
params = {
  "router": jax.random.normal(ks[0], (d, E)) * 0.02,
  "w_gate": jax.random.normal(ks[1], (E, d, f)) * d**-0.5,
  "w_up": jax.random.normal(ks[2], (E, d, f)) * d**-0.5,
  "w_down": jax.random.normal(ks[3], (E, f, d)) * f**-0.5,
}
x = jax.random.normal(ks[4], (2, 16, d)) * 0.5
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model=4)
with jax.set_mesh(mesh):
    ep = jax.jit(lambda p, xx: moe_ffn_ep(p, xx, cfg))(params, x)
ref = moe_ffn(params, x, cfg)
err = float(jnp.abs(ep - ref).max())
assert err < 1e-5, err
print("EP_OK", err)
"""
    out = run8(code)
    assert "EP_OK" in out


def test_multipath_wakeup_lowering_subprocess():
    """make_wakeup_step: host-chunked staging -> serving layout lowers and
    emits ICI collectives (the TPU-native MMA relay schedule)."""
    code = r"""
import jax
from repro.configs import get_config
from repro.distributed import make_wakeup_step
cfg = get_config("tinyllama-1.1b").reduced()
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model=4)
fn, stage_sh, serve_sh = make_wakeup_step(cfg, mesh)
from repro.models.init import abstract_params
with jax.set_mesh(mesh):
    compiled = fn.lower(abstract_params(cfg)).compile()
hlo = compiled.as_text()
n_coll = sum(hlo.count(k) for k in ("all-gather", "collective-permute",
                                    "all-to-all"))
assert n_coll > 0, "expected ICI assembly collectives"
print("WAKEUP_OK", n_coll)
"""
    out = run8(code)
    assert "WAKEUP_OK" in out


def test_dryrun_one_combo_subprocess():
    """End-to-end dry-run smoke (the full 80-combo matrix runs via the
    CLI; this pins the integration): tinyllama x decode_32k on 512
    placeholder devices, single pod + multi pod."""
    code = r"""
from repro.launch.dryrun import dryrun_one
r1 = dryrun_one("tinyllama-1.1b", "decode_32k", multi_pod=False,
                verbose=False)
r2 = dryrun_one("tinyllama-1.1b", "decode_32k", multi_pod=True,
                verbose=False)
assert r1["ok"] and r2["ok"]
assert r1["n_chips"] == 256 and r2["n_chips"] == 512
assert r1["flops_per_device"] > 0
assert r1["dominant"] in ("compute", "memory", "collective")
print("DRYRUN_OK", r1["dominant"], r2["dominant"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, cwd=REPO, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN_OK" in out.stdout
