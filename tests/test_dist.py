"""Multi-device behaviour (8 fake devices, subprocess so the main test
session keeps 1 device): sharded train step, shard_map MoE == fallback,
compressed all-reduce correctness."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from repro.train.optimizer import (compress_grads, compression_init,
                                   decompress_grads)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run8(code: str, timeout=600) -> str:
    full = ('import os\n'
            'os.environ["XLA_FLAGS"] = '
            '"--xla_force_host_platform_device_count=8"\n'
            f'import sys\nsys.path.insert(0, {SRC!r})\n' + code)
    out = subprocess.run([sys.executable, "-c", full], capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = _run8("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.configs.base import smoke_config
from repro.dist import sharding as shd
from repro.launch import steps as step_lib
from repro.models import lm
from repro.train import data as data_lib, optimizer as opt

cfg = smoke_config(configs.get("codeqwen1.5-7b"))
batch = data_lib.batch_for_arch(cfg, 0, 0, 8, 32)
params, axes = lm.init_lm(jax.random.PRNGKey(0), cfg)

# single-device reference
loss_ref, _ = lm.lm_loss(params, batch, cfg)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = shd.make_rules("train")
with mesh, shd.shard_ctx(mesh, rules):
    p_sh = step_lib.param_shardings(mesh, rules, axes, params)
    params_s = jax.device_put(params, p_sh)
    loss_s, _ = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg))(params_s, batch)
err = abs(float(loss_ref) - float(loss_s)) / abs(float(loss_ref))
assert err < 2e-2, (float(loss_ref), float(loss_s))
print("SHARDED_LOSS_OK", err)
""")
    assert "SHARDED_LOSS_OK" in out


def test_shard_map_moe_matches_fallback():
    out = _run8("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.configs.base import smoke_config
from repro.dist import sharding as shd
from repro.launch import steps as step_lib
from repro.models import lm
from repro.train import data as data_lib

cfg = smoke_config(configs.get("olmoe-1b-7b"))
cfg = dataclasses.replace(cfg, moe_groups=8)   # 8 groups over 4-way data
params, axes = lm.init_lm(jax.random.PRNGKey(0), cfg)
batch = data_lib.batch_for_arch(cfg, 0, 0, 8, 32)
loss_ref, _ = lm.lm_loss(params, batch, cfg)   # fallback path (no mesh)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = shd.make_rules("train")
with mesh, shd.shard_ctx(mesh, rules):
    p_sh = step_lib.param_shardings(mesh, rules, axes, params)
    params_s = jax.device_put(params, p_sh)
    loss_s, _ = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg))(params_s, batch)
    # grads flow through the shard_map dispatch
    g = jax.jit(jax.grad(lambda p, b: lm.lm_loss(p, b, cfg)[0]))(params_s, batch)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
err = abs(float(loss_ref) - float(loss_s)) / abs(float(loss_ref))
assert err < 2e-2, (float(loss_ref), float(loss_s))
assert np.isfinite(gn) and gn > 0
print("MOE_SM_OK", err)
""")
    assert "MOE_SM_OK" in out


def test_multipod_mesh_runs_real_step():
    """(2,2,2) pod mesh: one real sharded train step executes on CPU."""
    out = _run8("""
import jax, jax.numpy as jnp
from repro import configs
from repro.configs.base import smoke_config
from repro.dist import sharding as shd
from repro.launch import steps as step_lib
from repro.models import lm
from repro.train import data as data_lib, optimizer as opt

cfg = smoke_config(configs.get("rwkv6-1.6b"))
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
rules = shd.make_rules("train", multi_pod=True)
with mesh, shd.shard_ctx(mesh, rules):
    params, axes = lm.init_lm(jax.random.PRNGKey(0), cfg)
    p_sh = step_lib.param_shardings(mesh, rules, axes, params)
    params = jax.device_put(params, p_sh)
    ostate = opt.adamw_init(params)
    step = jax.jit(step_lib.make_train_step(cfg, opt.AdamWConfig(lr=1e-3)),
                   donate_argnums=(0, 1))
    batch = data_lib.batch_for_arch(cfg, 0, 0, 4, 32)
    params, ostate, m = step(params, ostate, batch)
    l0 = float(m["loss"])
    batch = data_lib.batch_for_arch(cfg, 0, 1, 4, 32)
    params, ostate, m = step(params, ostate, batch)
assert l0 > 0 and float(m["loss"]) > 0
print("MULTIPOD_OK", l0, float(m["loss"]))
""")
    assert "MULTIPOD_OK" in out


def test_compressed_allreduce_error_feedback():
    """int8 + error feedback: mean error decays over repeated rounds."""
    rng = np.random.default_rng(0)
    grads = {"a": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    state = compression_init(grads)
    accum_q = jnp.zeros_like(grads["a"])
    accum_f = jnp.zeros_like(grads["a"])
    for _ in range(20):
        q, s, state = compress_grads(grads, state, nbits=8)
        deq = decompress_grads(q, s)
        accum_q = accum_q + deq["a"]
        accum_f = accum_f + grads["a"]
    # error feedback keeps the ACCUMULATED stream unbiased
    rel = float(jnp.linalg.norm(accum_q - accum_f)
                / jnp.linalg.norm(accum_f))
    assert rel < 1e-3, rel


def test_compressed_psum_inside_shard_map():
    out = _run8("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import compressed_psum_mean

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 7.0

def f(x_blk):
    m, _ = compressed_psum_mean(x_blk[0], "data", nbits=8)
    return m[None]

got = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                    check_vma=False)(x)
want = jnp.mean(x, axis=0)
err = float(jnp.max(jnp.abs(got[0] - want)) / jnp.max(jnp.abs(want)))
assert err < 2e-2, err
print("CPSUM_OK", err)
""")
    assert "CPSUM_OK" in out


def test_zero3_and_microbatch_train_step():
    """ZeRO-3 compute layout + grad-accum microbatching run sharded and
    reproduce the TP-layout loss."""
    out = _run8("""
import jax, jax.numpy as jnp
from repro import configs
from repro.configs.base import smoke_config
from repro.dist import sharding as shd
from repro.launch import steps as step_lib
from repro.models import lm
from repro.train import data as data_lib, optimizer as opt

cfg = smoke_config(configs.get("minitron-8b"))
batch = data_lib.batch_for_arch(cfg, 0, 0, 8, 32)
params, axes = lm.init_lm(jax.random.PRNGKey(0), cfg)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

losses = {}
for name, z3, nm in [("tp", False, 1), ("zero3", True, 1), ("zero3mb2", True, 2)]:
    rules = shd.make_rules("train", zero3=z3)
    with mesh, shd.shard_ctx(mesh, rules):
        p_sh = step_lib.param_shardings(mesh, rules, axes, params)
        # fresh copy per config: device_put may alias, and donation would
        # delete the shared buffers for the next config
        p = jax.device_put(jax.tree.map(jnp.array, params), p_sh)
        o = opt.adamw_init(p)
        step = jax.jit(step_lib.make_train_step(
            cfg, opt.AdamWConfig(lr=1e-3), n_micro=nm), donate_argnums=(0, 1))
        _, _, m = step(p, o, batch)
        losses[name] = float(m["loss"])
ref = losses["tp"]
for k, v in losses.items():
    assert abs(v - ref) / abs(ref) < 2e-2, losses
print("ZERO3_OK", losses)
""")
    assert "ZERO3_OK" in out


def test_sharded_qgraph_conv_matches_unsharded():
    """GNN path under shard_ctx: qgraph_conv feature-sharded over 8 devices
    reproduces the unsharded result bit-exactly (the aggregation GEMM is
    exact int32, the epilogue elementwise) for both integer backends."""
    out = _run8("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import api
from repro.api import nn as qnn
from repro.core.quantize import calibrate, quantize
from repro.dist import sharding as shd

rng = np.random.default_rng(0)
N, D, S = 64, 64, 3
adj = jnp.asarray((rng.random((N, N)) < 0.15).astype(np.int32))
adj = adj * (1 - jnp.eye(N, dtype=jnp.int32))        # no self loops
h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
qph = calibrate(h, S)
hq = quantize(h, qph)
inv_deg = 1.0 / (jnp.sum(adj, axis=1, keepdims=True).astype(jnp.float32) + 1)

mesh = jax.make_mesh((8, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = shd.make_rules("train")
for backend in ("popcount", "pallas"):
    with api.use(backend):
        want_cnt = np.asarray(api.bitserial_mm(adj, hq, 1, S))
        want = np.asarray(qnn.qgraph_conv(adj, hq, qph, inv_deg))
        with mesh, shd.shard_ctx(mesh, rules):
            def blk(hq_blk):
                cnt = api.bitserial_mm(adj, hq_blk, 1, S)
                out = qnn.qgraph_conv(adj, hq_blk, qph, inv_deg)
                return cnt, out
            got_cnt, got = jax.shard_map(
                blk, mesh=mesh, in_specs=P(None, "data"),
                out_specs=(P(None, "data"), P(None, "data")),
                check_vma=False)(hq)
        assert want_cnt.dtype == np.int32 and got_cnt.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(got_cnt), want_cnt)
        np.testing.assert_array_equal(np.asarray(got), want)
print("GNN_SHARD_OK")
""")
    assert "GNN_SHARD_OK" in out
