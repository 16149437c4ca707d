"""Fault tolerance: atomic checkpoints, retention, elastic restore,
failure-injection resume, straggler watchdog."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from repro.dist import checkpoint as ckpt
from repro.dist.elastic import StragglerWatchdog, replan_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(5.0), "step": jnp.int32(3)}}


def test_save_restore_bit_exact(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 10, t, cfg_hash="abc")
    restored, manifest = ckpt.restore(tmp_path, t, cfg_hash="abc")
    assert manifest["step"] == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention_and_latest(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, t, keep=2)
    assert ckpt.list_steps(tmp_path) == [4, 5]
    assert ckpt.latest_step(tmp_path) == 5


def test_cfg_hash_mismatch_rejected(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 1, t, cfg_hash="aaa")
    with pytest.raises(ValueError, match="cfg_hash"):
        ckpt.restore(tmp_path, t, cfg_hash="bbb")


def test_structure_mismatch_rejected(tmp_path):
    ckpt.save(tmp_path, 1, _tree())
    bad = {"w": jnp.zeros((8, 16))}
    with pytest.raises(ValueError, match="leaf count"):
        ckpt.restore(tmp_path, bad)


def test_interrupted_write_never_corrupts(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 1, t)
    # simulate a crash mid-write: a stale .tmp dir must be ignored/cleaned
    tmp = tmp_path / "step_0000000002.tmp"
    tmp.mkdir()
    (tmp / "leaf_00000.npy").write_bytes(b"garbage")
    assert ckpt.latest_step(tmp_path) == 1
    restored, m = ckpt.restore(tmp_path, t)
    assert m["step"] == 1


def test_elastic_restore_across_meshes(tmp_path):
    """Save on an 8-device (4,2) mesh, restore onto (2,4) and (8,1)."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, {SRC!r})
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist import checkpoint as ckpt

t = {{"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.arange(8.0)}}
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sh = {{"w": NamedSharding(mesh, P("data", "model")),
      "b": NamedSharding(mesh, P("model"))}}
t_sharded = jax.device_put(t, sh)
ckpt.save({str(tmp_path)!r}, 5, t_sharded, mesh_shape=mesh.shape)

for shape in [(2, 4), (8, 1), (1, 1)]:
    mesh2 = jax.make_mesh(shape, ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh2 = {{"w": NamedSharding(mesh2, P("data", "model")),
           "b": NamedSharding(mesh2, P("model"))}}
    restored, m = ckpt.restore({str(tmp_path)!r}, t, shardings=sh2)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0).reshape(8, 8))
    np.testing.assert_array_equal(np.asarray(restored["b"]), np.arange(8.0))
print("ELASTIC_OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


def test_replan_mesh():
    assert replan_mesh(256, 16) == (16, 16)
    assert replan_mesh(240, 16) == (8, 16)   # lost a host -> shrink data
    assert replan_mesh(8, 1) == (8, 1)
    with pytest.raises(ValueError):
        replan_mesh(4, 8)


def test_straggler_watchdog_flags_outliers():
    w = StragglerWatchdog(tolerance=2.0)
    for i in range(10):
        assert not w.observe(i, 0.1)
    assert w.observe(10, 0.5)  # 5x p50
    assert w.flagged[0]["step"] == 10
    assert w.p95 >= w.p50


def test_train_failure_injection_and_resume(tmp_path):
    """Kill training mid-run (exit 17), rerun, verify it resumes and
    finishes with the same deterministic data stream."""
    env = dict(os.environ, PYTHONPATH=SRC)
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "rwkv6-1.6b", "--smoke", "--steps", "12", "--batch", "2",
            "--seq", "32", "--ckpt-every", "4", "--ckpt-dir",
            str(tmp_path), "--log-every", "2"]
    r1 = subprocess.run(args + ["--simulate-failure-at", "6"],
                        capture_output=True, text=True, env=env, timeout=600)
    assert r1.returncode == 17, r1.stderr[-2000:]
    assert "SIMULATED FAILURE" in r1.stdout
    r2 = subprocess.run(args, capture_output=True, text=True, env=env,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 4" in r2.stdout
    # the final checkpoint exists at step 12
    from repro.dist import checkpoint as ckpt
    assert ckpt.latest_step(tmp_path) == 12
