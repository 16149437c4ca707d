"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each kernel for a topology it
is only told about, so whatever Mosaic refuses (block shapes off the
(8, 128) tiling, primitives with no lowering, unsupported casts) fails here
instead of on the chip. Shapes are the largest serving bucket of the
full-scale ogbn-arxiv smoke (``n_pad`` = 512 nodes, 128 input features,
the paper GCN's hidden width 16, 8-bit features and weights).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api

N_PAD, IN_DIM, HIDDEN, BITS = 512, 128, 16, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _chip_policy(**kw):
    # the policy the chip resolves: the default grid, compiled (not
    # interpreted -- this process's backend is the CPU)
    return api.DEFAULT_POLICY.replace(interpret=False, **kw)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _u32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


@pytest.mark.parametrize("jump", ["none", "mask", "compact", "sgt"])
def test_bitserial_gemm_compiles(one_chip, jump):
    """Aggregation GEMM: 1-bit adjacency x 8-bit features, every jump mode."""
    pol = _chip_policy(jump=jump)
    text = _compile_text(
        lambda a, b: api.bitserial_mm_packed(a, b, backend="pallas",
                                             policy=pol),
        _u32(one_chip, 1, N_PAD, N_PAD // 32),
        _u32(one_chip, BITS, N_PAD // 32, IN_DIM))
    assert "tpu_custom_call" in text


def test_bitserial_gemm_mxu_compiles(one_chip):
    pol = _chip_policy(mode="mxu")
    text = _compile_text(
        lambda a, b: api.bitserial_mm_packed(a, b, backend="pallas",
                                             policy=pol),
        _u32(one_chip, BITS, N_PAD, IN_DIM // 32),
        _u32(one_chip, BITS, IN_DIM // 32, HIDDEN))
    assert "tpu_custom_call" in text


def test_bitserial_fused_compiles(one_chip):
    pol = _chip_policy()
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda a, b, al, be: api.bitserial_fused(
            a, b, al, be, out_bits=BITS, backend="pallas", policy=pol),
        _u32(one_chip, BITS, N_PAD, IN_DIM // 32),
        _u32(one_chip, BITS, IN_DIM // 32, HIDDEN),
        f32(N_PAD, 1), f32(1, HIDDEN))
    assert "tpu_custom_call" in text


def test_bitpack_compiles(one_chip):
    pol = _chip_policy()
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda x, sc, z: api.bitpack(x, sc, z, nbits=BITS, backend="pallas",
                                     policy=pol),
        f32(N_PAD, IN_DIM), f32(), f32())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(N_PAD, HIDDEN, IN_DIM),   # g @ w.T
                                   (IN_DIM, N_PAD, HIDDEN)])  # h.T @ g
def test_backward_bitserial_mm_compiles(one_chip, shape):
    """The integer backward GEMMs of ``api.nn.qlinear_train``."""
    m, k, n = shape
    pol = _chip_policy()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    text = _compile_text(
        lambda a, b: api.bitserial_mm(a, b, BITS, BITS, backend="pallas",
                                      policy=pol),
        i32(m, k), i32(k, n))
    assert "tpu_custom_call" in text


GIN_HIDDEN, N_CLASSES = 64, 40


@pytest.mark.parametrize("m,k,n,a_bits", [
    (N_PAD, N_PAD, GIN_HIDDEN, 1),       # aggregation at the hidden width
    (N_PAD, IN_DIM, GIN_HIDDEN, BITS),   # layer 0's first MLP GEMM
    (N_PAD, GIN_HIDDEN, GIN_HIDDEN, BITS),
    (N_PAD, GIN_HIDDEN, N_CLASSES, BITS)])  # the last layer's second GEMM
def test_gin_forward_gemms_compile(one_chip, m, k, n, a_bits):
    """The paper GIN's (hidden 64) integer GEMMs, as ``api.nn`` calls them."""
    pol = _chip_policy()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    text = _compile_text(
        lambda a, b: api.bitserial_mm(a, b, a_bits, BITS, backend="pallas",
                                      policy=pol),
        i32(m, k), i32(k, n))
    assert "tpu_custom_call" in text


# ------------------------------------------------- no fallback hides the chip

def _load_chip_smoke():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_a_tpu(capsys):
    """On the CPU the smoke exits non-zero, names the missing TPU and
    prints no result line."""
    rc = _load_chip_smoke().main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    assert '"ok"' not in out


def test_cpu_tuning_table_has_no_opinion_on_a_tpu(monkeypatch):
    from repro.tune import table as tune_table

    table = tune_table.default_table()
    assert table is not None and table.meta["jax_backend"] == "cpu"
    query = dict(bits=8, shape=(512, 512, IN_DIM))
    assert table.policy_for("bitserial_mm", **query) is not None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert table.policy_for("bitserial_mm", **query) is None
    assert tune_table.dispatch_policy("bitserial_mm", **query) is None


def test_default_backend_is_pallas_on_a_tpu(monkeypatch):
    """With no engine chosen, the launchers' GEMMs run the paper's kernels
    on a TPU and ``xla_dot`` elsewhere."""
    from repro.api import registry

    monkeypatch.setattr(registry, "_default", (None, api.DEFAULT_POLICY))
    assert api.current()[0].name == "xla_dot"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert api.current()[0].name == "pallas"
    be, _ = api.resolve("bitserial_mm", s=8, t=8)
    assert be.name == "pallas"


def test_interpret_mode_on_a_tpu_is_an_error(monkeypatch):
    from repro.kernels import ops as kops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        kops.bitpack(jnp.zeros((8, 32)), 1.0, 0.0, nbits=1,
                     policy=api.DEFAULT_POLICY.replace(interpret=True))
