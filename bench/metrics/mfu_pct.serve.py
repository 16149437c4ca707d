"""Model operations of every request answered by the window's close, per
second of the window, over the chip's int8 peak: the whole serving step's
share of the peak."""
from bench.cost import gnn as cost
from bench.stats import peaks


def read(rec):
    if rec.get("kind") != "serve" or rec["window_s"] <= 0:
        return None
    ops = sum(cost.request_ops(rec["cfg"], n)
              for (_, end), sizes in zip(rec["step_times"], rec["step_sizes"])
              if end <= rec["t_close"] for n in sizes)
    pk = peaks(rec["device_kind"])
    return 100.0 * ops / rec["window_s"] / pk["int8_ops_per_s"]
