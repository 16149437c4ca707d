"""jit'd public wrappers around the Pallas kernels: padding, jump-mode
plumbing, output cropping, and CPU-interpret dispatch.

On CPU backends the kernels execute under interpret=True (exact, slow);
on TPU they always compile to Mosaic. All wrappers are shape-polymorphic
over inputs but keep block sizes static. ``bgemm`` is the one-plane case
of the bit-serial kernel.

Tunables come from an ``repro.api.ExecutionPolicy`` (``policy=``); explicit
keyword overrides (``block_m=``, ``jump=``, ...) win over the policy, which
wins over DEFAULT_POLICY. The public wrappers resolve the policy eagerly and
call inner jitted functions with static ints, so two calls with equal
policies share one compiled executable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.api.policy import DEFAULT_POLICY, ExecutionPolicy
from repro.core import bitops, zerotile
from repro.kernels import bitpack as _bitpack
from repro.kernels import bitserial as _bitserial
from repro.kernels import sgt as _sgt
from repro.kernels import wqmm as _wqmm

__all__ = ["bgemm", "bitserial_gemm", "bitserial_fused", "bitpack",
           "wq_gemm", "edge_scatter_sum", "auto_interpret"]


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(policy: ExecutionPolicy | None, **overrides):
    """Merge explicit kwargs over the policy over DEFAULT_POLICY.

    ``interpret`` None resolves to the Pallas interpreter everywhere but
    on a TPU. On a TPU the kernels always compile: asking for the
    interpreter there is an error, never a silent slow path.
    """
    pol = policy if policy is not None else DEFAULT_POLICY
    out = {k: (v if v is not None else getattr(pol, k))
           for k, v in overrides.items()}
    if "interpret" in out:
        if out["interpret"] is None:
            out["interpret"] = auto_interpret()
        elif out["interpret"] and not auto_interpret():
            raise ValueError(
                "interpret=True on a TPU: the Pallas kernels compile for the "
                "chip here; leave interpret=None")
    return out


def _pad2(x, bm, bw, axes=(0, 1)):
    x = bitops.pad_to(x, axes[0], bm)
    return bitops.pad_to(x, axes[1], bw)


def _unpack_tiles(tiles):
    """tiles=(idx, counts, s_max[, kind]) -> (idx, counts, static int, kind).

    ``kind`` tags which remap the arrays are: ``"compact"`` (the default,
    block_w-word k-TILE ids from ``zerotile.compact_artifacts``) or
    ``"sgt"`` (single-WORD column ids from ``sgt.sgt_artifacts``). The
    kind, like ``s_max``, is jit-static — it selects the kernel schedule.
    """
    if tiles is None:
        return None, None, 0, "compact"
    if len(tiles) == 4:
        idx, cnt, s_max, kind = tiles
    else:
        (idx, cnt, s_max), kind = tiles, "compact"
    if kind not in ("compact", "sgt"):
        raise ValueError(
            f"tiles kind must be 'compact' or 'sgt', got {kind!r}")
    if not isinstance(s_max, int):
        raise TypeError(
            f"tiles s_max must be a host int (it sizes the kernel grid), "
            f"got {type(s_max).__name__}")
    return idx, cnt, s_max, kind


def bgemm(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    policy: ExecutionPolicy | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_w: int | None = None,
    mode: str | None = None,
    jump: str | None = None,  # none | mask | compact | sgt
    tiles: tuple | None = None,      # precomputed (idx, counts, s_max[, kind])
    occupancy: jax.Array | None = None,  # precomputed (MT, KT) mask
    interpret: bool | None = None,
) -> jax.Array:
    """1-bit GEMM (M,W)x(W,N)->int32 with optional zero-tile jumping.

    ``tiles``/``occupancy`` supply PREcomputed jump artifacts (e.g. from the
    serve tile cache) so the jitted call does no occupancy analysis; they
    take precedence over the ``jump`` mode, which recomputes them in-call
    (a precomputed ``occupancy`` also short-circuits ``jump="compact"``'s
    in-call reduction). ``tiles`` may be the tagged 4-tuple from
    ``sgt.sgt_artifacts`` to select the sparse-graph-translation kernel.
    """
    return bitserial_gemm(a_packed[None], b_packed[None], policy=policy,
                          block_m=block_m, block_n=block_n, block_w=block_w,
                          mode=mode, jump=jump, tiles=tiles,
                          occupancy=occupancy, interpret=interpret)


def _bitserial_jump_artifacts(a, tiles_idx, tiles_cnt, occupancy, jump,
                              block_m, block_w, s_max, tiles_kind):
    """Resolve (occupancy, compact, sgt) for a padded (s, M, W) operand.

    Precomputed artifacts win over the ``jump`` mode (which recomputes them
    in-call from the OR of A's bit planes — exact for any bitwidth), and a
    precomputed ``occupancy`` map short-circuits ``jump="compact"``'s
    in-call OR-reduction: the documented precedence is
    tiles > occupancy > recompute, never recompute what the caller cached.
    """
    if tiles_idx is not None:
        if tiles_kind == "sgt":
            return None, None, (tiles_idx, tiles_cnt, s_max)
        return None, (tiles_idx, tiles_cnt, s_max), None
    if jump == "sgt":
        # word-granularity translation; a tile-granularity occupancy map
        # cannot seed it (wrong grid), so this recomputes from the planes
        wocc = _sgt.word_occupancy(a, block_m)
        idx, cnt = zerotile.compact_tiles(wocc)
        return None, None, (idx, cnt, wocc.shape[1])
    if jump == "compact":
        occ = (occupancy if occupancy is not None
               else zerotile.tile_occupancy_planes(a, block_m, block_w))
        idx, cnt = zerotile.compact_tiles(occ)
        return None, (idx, cnt, occ.shape[1]), None
    if occupancy is not None:
        return occupancy, None, None
    if jump == "mask":
        return zerotile.tile_occupancy_planes(a, block_m, block_w), None, None
    return None, None, None


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_w",
                                             "mode", "jump", "s_max",
                                             "tiles_kind", "interpret"))
def _bitserial_gemm_call(a_packed, b_packed, tiles_idx, tiles_cnt, occupancy,
                         *, block_m, block_n, block_w, mode, jump, s_max,
                         tiles_kind, interpret):
    _, m, _ = a_packed.shape
    _, _, n = b_packed.shape
    a = _pad2(a_packed, block_m, block_w, axes=(1, 2))
    b = _pad2(b_packed, block_w, block_n, axes=(1, 2))
    occ, compact, sgt = _bitserial_jump_artifacts(
        a, tiles_idx, tiles_cnt, occupancy, jump, block_m, block_w, s_max,
        tiles_kind)
    out = _bitserial.bitserial_gemm(a, b, block_m=block_m, block_n=block_n,
                                    block_w=block_w, mode=mode,
                                    occupancy=occ, compact=compact, sgt=sgt,
                                    interpret=interpret)
    return out[:m, :n]


def bitserial_gemm(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    policy: ExecutionPolicy | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_w: int | None = None,
    mode: str | None = None,
    jump: str | None = None,  # none | mask | compact | sgt
    tiles: tuple | None = None,      # precomputed (idx, counts, s_max[, kind])
    occupancy: jax.Array | None = None,  # precomputed (MT, KT) mask
    interpret: bool | None = None,
) -> jax.Array:
    """(s,M,W)x(t,W,N)->int32 exact any-bitwidth GEMM with zero-tile jumping.

    ``tiles``/``occupancy`` supply precomputed jump artifacts keyed to A's
    packed-and-padded tile grid (e.g. the serve cache's compact indices, or
    the tagged word-column remap from ``sgt.sgt_artifacts``); they take
    precedence over ``jump``, which recomputes them per call.
    """
    kw = _resolve(policy, block_m=block_m, block_n=block_n, block_w=block_w,
                  mode=mode, jump=jump, interpret=interpret)
    t_idx, t_cnt, s_max, kind = _unpack_tiles(tiles)
    return _bitserial_gemm_call(a_packed, b_packed, t_idx, t_cnt, occupancy,
                                s_max=s_max, tiles_kind=kind, **kw)


@functools.partial(jax.jit, static_argnames=("out_bits", "relu", "block_m",
                                             "block_n", "block_w", "mode",
                                             "jump", "s_max", "tiles_kind",
                                             "interpret"))
def _bitserial_fused_call(a_packed, b_packed, alpha, beta, tiles_idx,
                          tiles_cnt, occupancy, *, out_bits, relu,
                          block_m, block_n, block_w, mode, jump, s_max,
                          tiles_kind, interpret):
    _, m, _ = a_packed.shape
    _, _, n = b_packed.shape
    a = _pad2(a_packed, block_m, block_w, axes=(1, 2))
    b = _pad2(b_packed, block_w, block_n, axes=(1, 2))
    # the §4.5 epilogue scale/shift operands are float by design
    # lint: allow[kernel-int-purity]
    al = bitops.pad_to(alpha.astype(jnp.float32).reshape(m, 1), 0, block_m)
    # lint: allow[kernel-int-purity]
    be = bitops.pad_to(beta.astype(jnp.float32).reshape(1, n), 1, block_n)
    occ, compact, sgt = _bitserial_jump_artifacts(
        a, tiles_idx, tiles_cnt, occupancy, jump, block_m, block_w, s_max,
        tiles_kind)
    out = _bitserial.bitserial_fused(a, b, al, be, out_bits=out_bits,
                                     relu=relu, block_m=block_m,
                                     block_n=block_n, block_w=block_w,
                                     mode=mode, occupancy=occ,
                                     compact=compact, sgt=sgt,
                                     interpret=interpret)
    return out[:m, :n]


def bitserial_fused(
    a_packed: jax.Array,
    b_packed: jax.Array,
    alpha: jax.Array,
    beta: jax.Array,
    *,
    out_bits: int,
    relu: bool = True,
    policy: ExecutionPolicy | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_w: int | None = None,
    mode: str | None = None,
    jump: str | None = None,  # none | mask | compact | sgt
    tiles: tuple | None = None,      # precomputed (idx, counts, s_max[, kind])
    occupancy: jax.Array | None = None,  # precomputed (MT, KT) mask
    interpret: bool | None = None,
) -> jax.Array:
    """Any-bit GEMM with fused rescale+ReLU+requantize epilogue (§4.5).

    Jump artifacts behave exactly as in :func:`bitserial_gemm`; the fused
    epilogue still runs on the last grid step for every output block.
    """
    kw = _resolve(policy, block_m=block_m, block_n=block_n, block_w=block_w,
                  mode=mode, jump=jump, interpret=interpret)
    t_idx, t_cnt, s_max, kind = _unpack_tiles(tiles)
    return _bitserial_fused_call(a_packed, b_packed, alpha, beta, t_idx,
                                 t_cnt, occupancy, out_bits=out_bits,
                                 relu=relu, s_max=s_max, tiles_kind=kind,
                                 **kw)


@functools.partial(jax.jit, static_argnames=("nbits", "block_m",
                                             "interpret"))
def _bitpack_call(x, scale, zero, *, nbits, block_m, interpret):
    m, k = x.shape
    xp = _pad2(x, block_m, 32)
    out = _bitpack.bitpack(xp, scale, zero, nbits, k_true=k, block_m=block_m,
                           interpret=interpret)
    return out[:, :m, :]


def bitpack(
    x: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    *,
    nbits: int,
    policy: ExecutionPolicy | None = None,
    block_m: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Quantize + pack (M,K) f32 -> (nbits, M, ceil(K/32)) uint32.

    A kernel block spans whole rows, so only ``block_m`` of the policy
    applies.
    """
    kw = _resolve(policy, block_m=block_m, interpret=interpret)
    return _bitpack_call(x, scale, zero, nbits=nbits, **kw)


@functools.partial(jax.jit, static_argnames=("group", "block_m", "block_n",
                                             "block_k", "interpret"))
def _wq_gemm_call(x, w_packed, scales, *, group, block_m, block_n, block_k,
                  interpret):
    m, k = x.shape
    n = w_packed.shape[1] * 2
    xp = _pad2(x, block_m, block_k)
    wp = bitops.pad_to(bitops.pad_to(w_packed, 0, block_k), 1, block_n // 2)
    sp = bitops.pad_to(bitops.pad_to(scales, 0, block_k // group), 1, block_n)
    out = _wqmm.wq_gemm(xp, wp, sp, group=group, block_m=block_m,
                        block_n=block_n, block_k=block_k,
                        interpret=interpret)
    return out[:m, :n]


def wq_gemm(
    x: jax.Array,
    w_packed: jax.Array,
    scales: jax.Array,
    *,
    group: int = 32,
    policy: ExecutionPolicy | None = None,
    block_m: int = 8,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """x (M,K) @ 4-bit packed W (K,N) -> f32 (M,N), dequant inside VMEM.

    Tile sizes keep their own defaults (the packed-nibble layout wants a
    wider N block than the bit-serial kernels); only ``interpret`` is read
    from the policy.
    """
    kw = _resolve(policy, interpret=interpret)
    return _wq_gemm_call(x, w_packed, scales, group=group, block_m=block_m,
                         block_n=block_n, block_k=block_k, **kw)


def edge_scatter_sum(values: jax.Array, src: jax.Array, dst: jax.Array,
                     n_out: int) -> jax.Array:
    """Edge-list aggregation: out[dst[e]] += values[src[e]], -1-padded edges.

    Dtype-preserving (int32 in -> int32 out), so the integer training path
    can fold a sparse remainder — the few cross-partition edges its blocked
    per-partition GEMMs do not cover — into the exact integer neighbor sum
    without leaving the integer domain. XLA's native gather/scatter is the
    right engine for a few-thousand-edge remainder on every backend (a
    Pallas scatter kernel would be all grid overhead at this size); keeping
    the seam here means a TPU kernel can replace it without touching
    callers.
    """
    valid = (src >= 0)[:, None]
    msgs = jnp.where(valid, values[jnp.clip(src, 0)], 0)
    out = jnp.zeros((n_out,) + values.shape[1:], values.dtype)
    return out.at[jnp.clip(dst, 0)].add(msgs)
