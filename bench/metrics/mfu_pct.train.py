"""Model operations of every training step in the window (forward and
backward, 3x the forward), per second, over the chip's int8 peak."""
from bench.cost import gnn as cost
from bench.stats import peaks


def read(rec):
    if rec.get("kind") != "train" or rec["window_s"] <= 0:
        return None
    cfg = dict(rec["cfg"], grad_bits=rec["mix"]["grad_bits"])
    ops = sum(cost.train_step_cost(cfg, b["sizes"], b["cross_edges"])
              ["model_ops"] for b in rec["step_batches"])
    return 100.0 * ops / rec["window_s"] / peaks(rec["device_kind"])[
        "int8_ops_per_s"]
