"""The bit-serial GEMM kernel's share of its roofline while serving.

Kernel time is the trace's time in the kernel; its operations and bytes
are those of every batch of the window (``bench/cost/gnn.py``); the
roofline is the larger of operations over the chip's int8 peak and bytes
over its HBM bandwidth (``bench/peaks.json``).
"""
from bench.cost import gnn as cost
from bench.stats import peaks
from bench.trace import kernel_seconds

KERNEL = r"/_bitserial_(gemm|fused)_call$"


def read(rec):
    t = rec.get("trace") if rec.get("kind") == "serve" else None
    if not t:
        return None
    secs = kernel_seconds(t, KERNEL)
    if not secs:
        return None
    ops = nbytes = 0
    for sizes in rec["step_sizes"]:
        o, b = cost.batch_cost(rec["cfg"], sizes)
        ops += o
        nbytes += b
    pk = peaks(rec["device_kind"])
    least = max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
