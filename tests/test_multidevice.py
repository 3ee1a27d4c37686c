"""Real multi-device partitioning tests, run in a subprocess with
--xla_force_host_platform_device_count=8 so the main pytest process keeps
the default 1-device view (per the project brief)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel import collectives as C
from repro.parallel.sharding import mesh_axes, tree_shardings, zero1_spec
from repro.models import api
from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 3)
rng = np.random.default_rng(0)

# 1) vocab-sharded lookup == plain take, and grads match
V, D = 32, 16
tab = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
ids = jnp.asarray(rng.integers(0, V, (4, 6)), jnp.int32)
with jax.set_mesh(mesh):
    tab_sh = jax.device_put(tab, NamedSharding(mesh, P("model", None)))
    got = C.vocab_sharded_lookup(tab_sh, ids, mesh)
    g1 = jax.grad(lambda t: (C.vocab_sharded_lookup(t, ids, mesh) ** 2).sum())(tab_sh)
want = jnp.take(tab, ids, axis=0)
g2 = jax.grad(lambda t: (jnp.take(t, ids, axis=0) ** 2).sum())(tab)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)
print("lookup OK")

# 2) sharded xent == direct xent
B, S, Dm, Vp = 4, 16, 8, 40
x = jnp.asarray(rng.standard_normal((B, S, Dm)).astype(np.float32))
head = jnp.asarray(rng.standard_normal((Dm, Vp)).astype(np.float32))
labels = jnp.asarray(rng.integers(0, 33, (B, S)), jnp.int32)
with jax.set_mesh(mesh):
    head_sh = jax.device_put(head, NamedSharding(mesh, P(None, "model")))
    loss = jax.jit(lambda x_, h_: C.sharded_xent_loss(x_, h_, labels,
                   true_vocab=33, seq_chunk=8))(x, head_sh)
logits = x @ head
logits = jnp.where(jnp.arange(Vp) < 33, logits, -jnp.inf)
lse = jax.nn.logsumexp(logits, axis=-1)
ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
want = jnp.mean(lse - ll)
np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
print("xent OK")

# 3) full smoke train step on (2,4) mesh with ZeRO-1 == single-device step
from repro.launch import steps as SS
cfg = get_smoke_config("mixtral-8x7b")
shape = ShapeSpec("t", 16, 4, "train")
batch = api.synth_batch(cfg, shape)
with jax.set_mesh(mesh):
    ax = mesh_axes(mesh)
    params = api.init(cfg, jax.random.key(0), ax)
    train_step, specs, opt = SS.make_train_step(cfg, mesh, lr=1e-2)
    sh_p = tree_shardings(mesh, specs["params"])
    params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, sh_p)
    opt_state = opt.init(params)
    p2, o2, metrics = jax.jit(train_step)(params, opt_state, batch)
assert np.isfinite(float(metrics["loss"]))
# reference on 1-device submesh logic: same math with mesh1
mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh1):
    params1 = api.init(cfg, jax.random.key(0), mesh_axes(mesh1))
    ts1, _, opt1 = SS.make_train_step(cfg, mesh1, lr=1e-2)
    p1, o1, m1 = jax.jit(ts1)(params1, opt1.init(params1), batch)
np.testing.assert_allclose(float(metrics["loss"]), float(m1["loss"]), rtol=2e-4)
print("train-step OK", float(metrics["loss"]))

# 4) hierarchical psum == plain psum; ef-int8 approximates with feedback
from repro.parallel.collectives import hierarchical_psum, ef_int8_psum
g = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
def plain(x):
    return jax.lax.psum(x, ("pod", "data"))
with jax.set_mesh(mesh3):
    f_h = jax.shard_map(hierarchical_psum, mesh=mesh3,
        in_specs=P(("pod", "data"), None), out_specs=P(("pod", "data"), None))
    f_p = jax.shard_map(plain, mesh=mesh3,
        in_specs=P(("pod", "data"), None), out_specs=P(("pod", "data"), None))
    np.testing.assert_allclose(np.asarray(f_h(g)), np.asarray(f_p(g)), rtol=1e-6)
    f_q = jax.shard_map(lambda gg, ee: ef_int8_psum(gg, ee), mesh=mesh3,
        in_specs=(P(("pod", "data"), None), P()),
        out_specs=(P(("pod", "data"), None), P(("pod", "data"), None)))
    got_q, err1 = f_q(g, jnp.zeros((), jnp.float32))
    exact = np.asarray(f_p(g))
    rel = np.abs(np.asarray(got_q) - exact).max() / (np.abs(exact).max() + 1e-9)
    assert rel < 0.05, rel
    # residual state is one in-pod scatter shard per device: global rows =
    # rows / npod (scatter halves the per-device rows, gather-by-spec x4)
    assert err1.shape == (g.shape[0] // 2, g.shape[1])
print("gradsync OK")
print("ALL-MULTIDEVICE-OK")
"""


@pytest.mark.slow
def test_multidevice_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ALL-MULTIDEVICE-OK" in r.stdout
