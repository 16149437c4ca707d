"""Built-in execution backends: xla_dot, popcount, pallas.

  xla_dot  — per-bit-plane int8 dot products through XLA (MXU emulation);
             portable, fast on any jax backend; registered first so it is
             the default off a TPU and the capability-fallback of last
             resort.
  popcount — packed AND+popcount in pure jnp: the paper's bit-serial
             VPU semantics, bit-exact oracle for the kernels.
  pallas   — the TPU Pallas kernels (kernels/ops.py): tiled bit-serial
             GEMM with zero-tile jumping, tile reuse and fused epilogues;
             the default on a TPU; runs under interpret mode off-TPU.

All three produce IDENTICAL int32 results for any (s, t) in 1..8 — that is
the repo's core exactness invariant, enforced by tests/test_api_dispatch.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.api.backend import Backend
from repro.api.registry import register

__all__ = ["XlaDotBackend", "PopcountBackend", "PallasBackend"]

_CORE_OPS = frozenset({"bitserial_mm", "bgemm", "bitpack", "bitserial_fused"})


def _fused_epilogue(acc, alpha, beta, out_bits: int, relu: bool):
    """alpha*acc+beta -> (relu) -> floor+clip to unsigned out_bits (§4.5)."""
    y = acc.astype(jnp.float32) * alpha + beta
    if relu:
        y = jnp.maximum(y, 0.0)
    return jnp.clip(jnp.floor(y), 0, (1 << out_bits) - 1).astype(jnp.int32)


def _jnp_bitpack(x, scale, zero, nbits: int):
    """Quantize (Eq. 2) + pack planes: (M,K) f32 -> (nbits, M, ceil(K/32))."""
    from repro.core import bitops

    q = jnp.clip(jnp.floor((x - zero) / scale), 0, (1 << nbits) - 1)
    return bitops.pack_a(q.astype(jnp.int32), nbits)


class XlaDotBackend(Backend):
    name = "xla_dot"
    capabilities = _CORE_OPS | {"wq_mm"}
    # the plane loop is bitwidth-agnostic; exactness is bounded only by the
    # int32 accumulator, same as the pre-registry implementation
    max_bits = 32

    def bitserial_mm_vals(self, aq, bq, s, t, *, policy):
        # One wide int32 dot over the bit-masked values. Algebraically
        # identical to the per-plane decomposition for EVERY int32 input —
        # plane i of bit_decompose reads exactly bit i, so the plane sum
        # only ever sees bits 0..s-1, which is what the mask keeps — but a
        # single dot_general instead of s*t int8 ones, which is what makes
        # the integer TRAINING path viable. The packed entry below keeps
        # the plane loop: that is the MXU-emulation semantics this backend
        # exists to model; unpacked values already paid materialization,
        # so the decomposition would be pure overhead.
        mask_a = (1 << s) - 1 if s < 32 else -1
        mask_b = (1 << t) - 1 if t < 32 else -1
        return jax.lax.dot_general(
            jnp.bitwise_and(aq, mask_a), jnp.bitwise_and(bq, mask_b),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    def bitserial_mm(self, a_packed, b_packed, *, policy):
        from repro.core import bitops

        # unpacking the words yields the bit planes directly
        a_planes = bitops.unpack_along_axis(a_packed, axis=2).astype(jnp.int8)
        b_planes = bitops.unpack_along_axis(b_packed, axis=1).astype(jnp.int8)
        s, t = a_planes.shape[0], b_planes.shape[0]
        m, n = a_planes.shape[1], b_planes.shape[2]
        acc = jnp.zeros((m, n), jnp.int32)
        for i in range(s):
            for j in range(t):
                prod = jax.lax.dot_general(
                    a_planes[i], b_planes[j], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc = acc + (prod << (i + j))
        return acc

    def bgemm(self, a_packed, b_packed, *, policy):
        return self.bitserial_mm(a_packed[None], b_packed[None], policy=policy)

    def bitpack(self, x, scale, zero, *, nbits, policy):
        return _jnp_bitpack(x, scale, zero, nbits)

    def wq_mm(self, x, wq, *, policy, out_dtype):
        xf = x.astype(jnp.float32)
        core = jnp.einsum("...k,kn->...n", xf, wq.data.astype(jnp.float32))
        rowsum = jnp.sum(xf, axis=-1, keepdims=True)
        return (core * wq.scale + rowsum * wq.zero).astype(out_dtype)

    def bitserial_fused(self, a_packed, b_packed, alpha, beta, *,
                        out_bits, relu, policy):
        acc = self.bitserial_mm(a_packed, b_packed, policy=policy)
        return _fused_epilogue(acc, alpha, beta, out_bits, relu)


class PopcountBackend(Backend):
    name = "popcount"
    capabilities = _CORE_OPS
    max_bits = 32  # bitwidth-agnostic plane loop (see XlaDotBackend)

    def bitserial_mm(self, a_packed, b_packed, *, policy):
        from repro.core import bitops

        return bitops.bitserial_matmul_packed(a_packed, b_packed)

    def bgemm(self, a_packed, b_packed, *, policy):
        from repro.core import bitops

        return bitops.popcount_matmul_packed(a_packed, b_packed)

    def bitpack(self, x, scale, zero, *, nbits, policy):
        return _jnp_bitpack(x, scale, zero, nbits)

    def bitserial_fused(self, a_packed, b_packed, alpha, beta, *,
                        out_bits, relu, policy):
        acc = self.bitserial_mm(a_packed, b_packed, policy=policy)
        return _fused_epilogue(acc, alpha, beta, out_bits, relu)


class PallasBackend(Backend):
    name = "pallas"
    capabilities = _CORE_OPS | {"bitserial_jump", "bitserial_sgt"}
    jump_modes = frozenset({"none", "mask", "compact", "sgt"})
    interpret_fallback = True

    def bitserial_mm(self, a_packed, b_packed, *, policy, tiles=None):
        from repro.kernels import ops as kops

        if not policy.reuse and a_packed.shape[0] * b_packed.shape[0] > 1:
            # §4.4 ablation: one 1-bit kernel pass per plane pair — A tiles
            # re-loaded O(s*t) times instead of once (the fig9a baseline).
            # Tiles are the plane-OR compact set, so they are valid (if
            # slightly conservative) for every individual plane.
            m, n = a_packed.shape[1], b_packed.shape[2]
            acc = jnp.zeros((m, n), jnp.int32)
            for i in range(a_packed.shape[0]):
                for j in range(b_packed.shape[0]):
                    acc = acc + (kops.bgemm(a_packed[i], b_packed[j],
                                            policy=policy,
                                            tiles=tiles) << (i + j))
            return acc
        return kops.bitserial_gemm(a_packed, b_packed, policy=policy,
                                   tiles=tiles)

    def bgemm(self, a_packed, b_packed, *, policy, tiles=None):
        from repro.kernels import ops as kops

        return kops.bgemm(a_packed, b_packed, policy=policy, tiles=tiles)

    def bitpack(self, x, scale, zero, *, nbits, policy):
        from repro.kernels import ops as kops

        return kops.bitpack(x, scale, zero, nbits=nbits, policy=policy)

    def bitserial_fused(self, a_packed, b_packed, alpha, beta, *,
                        out_bits, relu, policy, tiles=None):
        from repro.kernels import ops as kops

        return kops.bitserial_fused(a_packed, b_packed, alpha, beta,
                                    out_bits=out_bits, relu=relu,
                                    policy=policy, tiles=tiles)


register(XlaDotBackend())
register(PopcountBackend())
register(PallasBackend())
