"""Pallas TPU kernel: any-bitwidth GEMM by 1-bit composition (paper §3, §4.4).

    A_packed (s, M, W) uint32  x  B_packed (t, W, N) uint32  ->  C (M, N) int32
    C = sum_{i<s, j<t} 2^(i+j) * popcount_gemm(A_i, B_j)
    popcount_gemm(A, B)[m, n] = sum_w popcount(A[m, w] & B[w, n])

The 1-bit GEMM (paper Eq. 7, the b1-WMMA analogue) is the s = t = 1 case;
``kernels.ops.bgemm`` calls this kernel with one plane per operand.

Tile layout. A TPU block's last two dimensions must be multiples of
(8, 128) or the array's full extent. The paper's k-tile is ``block_w``
packed words (4 words = the 8x128 adjacency tile), which is neither on
A's word (lane) axis. So both operands are re-blocked with the k-tile
index as a leading dimension:

    A (s, M, W) -> (s, W/bw, M, bw)     block (s, ., block_m, bw)
    B (t, W, N) -> (t, W/bw, bw, N)     block (t, ., bw, block_n)

Inside a block ``bw`` is the full minor extent, so every tile width is
legal and each grid step still sees a (block_m, bw) tile of A and a
(bw, block_n) tile of B. Sparse-graph translation is the bw = 1 case.

Known cost of this layout, not yet timed: the re-blocking of A is an XLA
transpose in HBM on every call, and the re-blocked A keeps the chip's
(8, 128) tiled layout with ``bw`` as its minor dim, so each (block_m, bw)
tile is padded to 128 lanes. A's stored and DMA'd bytes are therefore
128/bw times its packed footprint: 32x at the default bw = 4, 128x for
SGT's bw = 1 (the compiled v5e layout is ``u32[s,W/bw,M,bw]`` with tile
T(8,128)). B keeps N as its lane axis and is not padded.

Compute modes (TPU adaptation of the 1-bit Tensor Core):
  'vpu' — bit-serial: one (BM, BN) popcount(AND) VPU op per packed word,
          statically unrolled over the tile's words (Mosaic lowers static
          slices, not ``dynamic_slice``).
  'mxu' — unpack bit-planes to int8 inside VMEM and issue one int8 MXU dot
          per tile (32x on-chip expansion on top of the layout cost above).

Non-zero tile reuse (§4.4 "cross-tile reduction") is structural here: for a
given (m, k) grid step the A tile words are DMA'd into VMEM once and the
loop over the s*t bit-plane pairs happens *inside* the kernel body, so tile
loads are O(1) in the bitwidth instead of O(s*t).

Zero-tile jumping (paper §4.3): occupancy is computed on the OR of A's bit
planes (for GNN aggregation A is the 1-bit adjacency), so a skipped tile is
zero in every plane and contributes nothing for any bitwidth.

  mask    — per-tile occupancy via scalar-prefetch SMEM; all-zero tiles
            skip the s*t plane products (pl.when) but their DMA still lands.
  compact — the K grid dimension is sized to the max non-zero tile count and
            a prefetched index array remaps the A AND B BlockSpec index_maps,
            so zero tiles are neither loaded nor computed (true DMA jumping).
  sgt     — sparse-graph translation (kernels/sgt.py, TC-GNN style): the
            same prefetched-remap machinery at single-WORD column
            granularity — the K grid visits only the non-zero word columns
            of each row window, so a tile with one nonzero word costs one
            step instead of block_w.

All variants accumulate into a VMEM scratch buffer and write the output
block once on the last K step — the int32 accumulator never round-trips
through the HBM-blocked ``o_ref`` between K steps.

``bitserial_fused`` adds the §4.5 inter-layer epilogue: on the last K step
the int32 accumulator is rescaled (alpha per-row — e.g. 1/degree for GNN
aggregation — and beta per-column, e.g. folded BatchNorm), ReLU'd, and
requantized to ``out_bits`` unsigned values, never round-tripping fp32
activations through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_W = 32


def _tile_product(a, b, mode: str):
    """(BM, BW) uint32 x (BW, BN) uint32 -> (BM, BN) int32 popcount GEMM."""
    bm, bw = a.shape
    bn = b.shape[1]
    if mode == "vpu":
        acc = jnp.zeros((bm, bn), jnp.int32)
        for w in range(bw):
            acc = acc + jax.lax.population_count(
                a[:, w:w + 1] & b[w:w + 1, :]).astype(jnp.int32)
        return acc
    if mode == "mxu":
        shifts = jnp.arange(32, dtype=jnp.uint32)
        a_bits = ((a[:, :, None] >> shifts[None, None, :]) & 1).astype(jnp.int8)
        a_bits = a_bits.reshape(bm, bw * 32)
        b_bits = ((b[:, None, :] >> shifts[None, :, None]) & 1).astype(jnp.int8)
        b_bits = b_bits.reshape(bw * 32, bn)
        return jax.lax.dot_general(
            a_bits, b_bits, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
    raise ValueError(f"unknown mode {mode!r}")


def _plane_accumulate(a_ref, b_ref, mode):
    """Accumulate all s*t shifted plane products for the resident tiles."""
    s, t = a_ref.shape[0], b_ref.shape[0]
    bm, bn = a_ref.shape[1], b_ref.shape[2]
    acc = jnp.zeros((bm, bn), jnp.int32)
    for i in range(s):          # static unroll: bit-planes of A
        a_i = a_ref[i]          # A tile loaded once, reused across j (§4.4)
        for j in range(t):      # static unroll: bit-planes of B
            acc = acc + (_tile_product(a_i, b_ref[j], mode) << (i + j))
    return acc


# lint: allow[kernel-int-purity] — the §4.5 fused requantize epilogue is
# the ONE sanctioned float region: rescale+clip happens in f32, the GEMM
# accumulator stays int32 (repro.analysis.trace proves no float dot_general)
def _store(acc_ref, o_ref, alpha_ref, beta_ref, *, out_bits, relu):
    """Write the accumulated block; fused §4.5 epilogue when alpha given."""
    if alpha_ref is None:
        o_ref[...] = acc_ref[...]
        return
    y = acc_ref[...].astype(jnp.float32) * alpha_ref[...] + beta_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    q = jnp.clip(jnp.floor(y), 0.0, float((1 << out_bits) - 1))
    o_ref[...] = q.astype(jnp.int32)


def _kernel(a_ref, b_ref, *rest, mode, kt, out_bits=0, relu=False):
    """Plain (dense) schedule; rest = (alpha?, beta?, o_ref, acc_ref)."""
    alpha_ref, beta_ref = (rest[0], rest[1]) if len(rest) == 4 else (None, None)
    o_ref, acc_ref = rest[-2], rest[-1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _plane_accumulate(a_ref, b_ref, mode)

    @pl.when(k == kt - 1)
    def _write():
        _store(acc_ref, o_ref, alpha_ref, beta_ref, out_bits=out_bits,
               relu=relu)


def _kernel_mask(occ_ref, a_ref, b_ref, *rest, mode, kt, out_bits=0,
                 relu=False):
    """Mask jumping: zero tiles skip the plane products, not the DMA."""
    alpha_ref, beta_ref = (rest[0], rest[1]) if len(rest) == 4 else (None, None)
    o_ref, acc_ref = rest[-2], rest[-1]
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[i, k] != 0)
    def _compute():
        acc_ref[...] += _plane_accumulate(a_ref, b_ref, mode)

    @pl.when(k == kt - 1)
    def _write():
        _store(acc_ref, o_ref, alpha_ref, beta_ref, out_bits=out_bits,
               relu=relu)


def _kernel_compact(idx_ref, cnt_ref, a_ref, b_ref, *rest, mode, s_max,
                    out_bits=0, relu=False):
    """Compact jumping: the grid's K dim only visits non-zero tiles."""
    alpha_ref, beta_ref = (rest[0], rest[1]) if len(rest) == 4 else (None, None)
    o_ref, acc_ref = rest[-2], rest[-1]
    i, s = pl.program_id(0), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < cnt_ref[i])
    def _compute():
        acc_ref[...] += _plane_accumulate(a_ref, b_ref, mode)

    @pl.when(s == s_max - 1)
    def _write():
        _store(acc_ref, o_ref, alpha_ref, beta_ref, out_bits=out_bits,
               relu=relu)


def _pallas_bitserial(a_packed, b_packed, alpha, beta, *, block_m, block_n,
                      block_w, mode, occupancy, compact, sgt, interpret,
                      out_bits, relu):
    """Shared pallas_call builder for the plain and fused entry points.

    ``alpha``/``beta`` None selects the raw-int32 output; otherwise the §4.5
    epilogue is fused into the final-K-step store.
    """
    s, m, w = a_packed.shape
    t, w2, n = b_packed.shape
    assert w == w2, (a_packed.shape, b_packed.shape)
    assert m % block_m == 0 and n % block_n == 0 and w % block_w == 0, (
        m, n, w, block_m, block_n, block_w)
    mt, nt, kt = m // block_m, n // block_n, w // block_w

    fused = alpha is not None
    if fused:
        assert alpha.shape == (m, 1) and beta.shape == (1, n)
    out_shape = jax.ShapeDtypeStruct((m, n), jnp.int32)
    scratch = [pltpu.VMEM((block_m, block_n), jnp.int32)]
    epi = dict(out_bits=out_bits, relu=relu)
    o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, k, *pre: (i, j))

    def operands(kw):
        # the k-tile index leads, so each block's minor dims are (block_m,
        # kw) / (kw, block_n) with kw the full extent (see module doc)
        a4 = a_packed.reshape(s, m, w // kw, kw).transpose(0, 2, 1, 3)
        b4 = b_packed.reshape(t, w // kw, kw, n)
        return [a4, b4, alpha, beta] if fused else [a4, b4]

    def specs(index_map, kw):
        sp = [
            pl.BlockSpec((s, None, block_m, kw),
                         lambda i, j, k, *pre: (0, index_map(i, k, *pre), i, 0)),
            pl.BlockSpec((t, None, kw, block_n),
                         lambda i, j, k, *pre: (0, index_map(i, k, *pre), 0, j)),
        ]
        if fused:
            sp += [pl.BlockSpec((block_m, 1), lambda i, j, k, *pre: (i, 0)),
                   pl.BlockSpec((1, block_n), lambda i, j, k, *pre: (0, j))]
        return sp

    remap = sgt if sgt is not None else compact
    if remap is not None:
        # compact and sgt share one schedule (init at s==0, compute under
        # s < count, write at s==s_max-1); sgt remaps single WORD columns
        # (kw = 1, so the block index IS the word id), compact remaps
        # block_w-word k-tiles
        kw, bound = (1, w) if sgt is not None else (block_w, kt)
        idx, cnt, s_max = remap
        s_max = max(int(s_max), 1)  # all-zero A: one guarded (no-op) step
        assert s_max <= bound, (s_max, bound)
        assert idx.shape[0] == mt and idx.shape[1] >= s_max and \
            cnt.shape == (mt,), (idx.shape, cnt.shape, mt, s_max)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(mt, nt, s_max),
            in_specs=specs(lambda i, k, idx_r, cnt_r: idx_r[i, k], kw),
            out_specs=o_spec,
            scratch_shapes=scratch,
        )
        kern = functools.partial(_kernel_compact, mode=mode, s_max=s_max,
                                 **epi)
        return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                              interpret=interpret)(idx, cnt, *operands(kw))

    if occupancy is not None:
        assert occupancy.shape == (mt, kt), (occupancy.shape, mt, kt)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mt, nt, kt),
            in_specs=specs(lambda i, k, occ_r: k, block_w),
            out_specs=o_spec,
            scratch_shapes=scratch,
        )
        kern = functools.partial(_kernel_mask, mode=mode, kt=kt, **epi)
        return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                              interpret=interpret)(occupancy,
                                                   *operands(block_w))

    kern = functools.partial(_kernel, mode=mode, kt=kt, **epi)
    return pl.pallas_call(
        kern,
        grid=(mt, nt, kt),
        in_specs=specs(lambda i, k: k, block_w),
        out_specs=o_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands(block_w))


def bitserial_gemm(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_w: int = DEFAULT_BLOCK_W,
    mode: str = "vpu",
    occupancy: jax.Array | None = None,
    compact: tuple[jax.Array, jax.Array, int] | None = None,
    sgt: tuple[jax.Array, jax.Array, int] | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Any-bitwidth GEMM. Shapes pre-padded to block multiples (ops.py pads).

    occupancy: (MT, KT) int32 0/1 -> mask-mode jumping.
    compact: (idx (MT, >=S), cnt (MT,), S) -> compact-mode jumping; S is the
    static K-grid size (max non-zero tile count; clamped to >= 1).
    sgt: (idx (MT, >=S_w), cnt (MT,), S_w) word-column remap from
    kernels/sgt.py -> sparse-graph translation; S_w is the static K-grid
    size (max non-zero WORD count per row window; clamped to >= 1).
    """
    return _pallas_bitserial(a_packed, b_packed, None, None, block_m=block_m,
                             block_n=block_n, block_w=block_w, mode=mode,
                             occupancy=occupancy, compact=compact, sgt=sgt,
                             interpret=interpret, out_bits=0, relu=False)


def bitserial_fused(
    a_packed: jax.Array,
    b_packed: jax.Array,
    alpha: jax.Array,  # (M, 1) f32 per-row scale (e.g. 1/degree)
    beta: jax.Array,   # (1, N) f32 per-col bias (e.g. folded BN)
    *,
    out_bits: int,
    relu: bool = True,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_w: int = DEFAULT_BLOCK_W,
    mode: str = "vpu",
    occupancy: jax.Array | None = None,
    compact: tuple[jax.Array, jax.Array, int] | None = None,
    sgt: tuple[jax.Array, jax.Array, int] | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Any-bit GEMM with fused rescale+ReLU+requantize epilogue (§4.5).

    Takes the same ``occupancy``/``compact``/``sgt`` jumping artifacts as
    ``bitserial_gemm``; the epilogue runs on the last grid step regardless
    of how many tiles (or word columns) were skipped.
    """
    return _pallas_bitserial(a_packed, b_packed, alpha, beta, block_m=block_m,
                             block_n=block_n, block_w=block_w, mode=mode,
                             occupancy=occupancy, compact=compact, sgt=sgt,
                             interpret=interpret, out_bits=out_bits,
                             relu=relu)
