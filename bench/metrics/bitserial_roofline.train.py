"""The bit-serial GEMM kernel's share of its roofline while training:
its operations and bytes over every step of the window
(``bench/cost/gnn.py``) against its time in the trace."""
from bench.cost import gnn as cost
from bench.stats import peaks
from bench.trace import kernel_seconds

KERNEL = r"/_bitserial_(gemm|fused)_call$"


def read(rec):
    t = rec.get("trace") if rec.get("kind") == "train" else None
    if not t:
        return None
    secs = kernel_seconds(t, KERNEL)
    if not secs:
        return None
    cfg = dict(rec["cfg"], grad_bits=rec["mix"]["grad_bits"])
    ops = nbytes = 0
    for b in rec["step_batches"]:
        c = cost.train_step_cost(cfg, b["sizes"], b["cross_edges"])
        ops += c["bitserial_ops"]
        nbytes += c["bitserial_bytes"]
    pk = peaks(rec["device_kind"])
    least = max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
