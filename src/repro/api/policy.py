"""ExecutionPolicy: every tunable of quantized-GEMM execution in one object.

Before this layer existed, tile sizes (``block_m/block_n/block_w``), the
zero-tile ``jump`` mode, compute ``mode`` and interpret fall-back were loose
kwargs re-plumbed at every call site. An ExecutionPolicy is a frozen,
hashable dataclass, so it can ride through ``jax.jit`` as a static argument
and be compared/deduped by value.

Fields map onto the paper's knobs:
  block_m/block_n/block_w — TC tile shape (paper's 8x128 tiles over packed
                            words; block_w counts uint32 words of K)
  mode                    — kernel compute unit: 'vpu' (popcount) | 'mxu'
  jump                    — zero-tile jumping (§4.3): none | mask | compact,
                            or 'sgt' — sparse-graph translation
                            (kernels/sgt.py): condense non-zero WORD
                            columns per row window, TC-GNN style
  reuse                   — non-zero tile reuse (§4.4): keep the s*t plane
                            loop inside one kernel so A-tile loads are O(1)
  fused_requantize        — fuse the §4.5 rescale+requantize epilogue into
                            the GEMM when the backend supports it
  interpret               — Pallas interpret mode; None = auto (interpret
                            off a TPU, compile on one). True on a TPU
                            raises: the chip never runs the interpreter
"""
from __future__ import annotations

import dataclasses

__all__ = ["ExecutionPolicy", "DEFAULT_POLICY", "JUMP_MODES", "COMPUTE_MODES"]

JUMP_MODES = ("none", "mask", "compact", "sgt")
COMPUTE_MODES = ("vpu", "mxu")


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    block_m: int = 8
    block_n: int = 128
    block_w: int = 4
    mode: str = "vpu"
    jump: str = "none"
    reuse: bool = True
    fused_requantize: bool = False
    interpret: bool | None = None

    def __post_init__(self):
        if self.jump not in JUMP_MODES:
            raise ValueError(f"jump must be one of {JUMP_MODES}, got {self.jump!r}")
        if self.mode not in COMPUTE_MODES:
            raise ValueError(f"mode must be one of {COMPUTE_MODES}, got {self.mode!r}")
        for f in ("block_m", "block_n", "block_w"):
            v = getattr(self, f)
            if not isinstance(v, int) or v <= 0:
                raise ValueError(f"{f} must be a positive int, got {v!r}")
        # Pack-width alignment, checked at construction so sweep-generated
        # candidate grids fail fast with a legible error instead of deep
        # inside the Pallas kernel builder. Operands are padded to block
        # multiples by the kernel wrappers, but the blocks themselves must
        # sit on the packed-word grid: A-tiles are (block_m, block_w)
        # uint32 words (8 sublanes of 32 K-bits each), B/N runs in
        # 128-lane units.
        if self.block_m % 8:
            raise ValueError(
                f"block_m must be a multiple of 8 (packed A-tile sublane "
                f"granularity), got {self.block_m}")
        if self.block_n % 128:
            raise ValueError(
                f"block_n must be a multiple of 128 (lane width of a "
                f"packed B tile), got {self.block_n}")

    def replace(self, **kw) -> "ExecutionPolicy":
        """Functional update (alias for dataclasses.replace)."""
        return dataclasses.replace(self, **kw)


DEFAULT_POLICY = ExecutionPolicy()
