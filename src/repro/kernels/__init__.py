# TPU Pallas kernels for the paper's compute hot-spots:
#   bitserial.py — any-bitwidth GEMM by 1-bit composition (1-bit bgemm is
#                  its one-plane case) + zero-tile jumping + non-zero tile
#                  reuse + fused quantize epilogue (§4.5)
#   bitpack.py   — quantize + 3D-stacked bit compression (§4.2)
# ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracles.
