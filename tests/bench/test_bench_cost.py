"""The cost functions count logical work and the peak table is strict."""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import stats  # noqa: E402
from bench.cost import gnn as cost  # noqa: E402

GCN = json.loads((ROOT / "bench/configs/qgtc-gcn-arxiv.json").read_text())
GIN = json.loads((ROOT / "bench/configs/qgtc-gin-arxiv.json").read_text())


def _plans(align, buckets, tile=128):
    """Member sizes of the batches the program's batcher forms from one
    request list, under a given alignment and bucket ladder."""
    from repro.serve.queue import MicroBatcher, SubgraphRequest

    rng = np.random.default_rng(0)
    sizes = rng.integers(101, 126, 13)
    mb = MicroBatcher(buckets, node_budget=512, edge_budget=1 << 14,
                      align=align, tile=tile)
    for n in sizes:
        mb.add(SubgraphRequest(edges=np.zeros((2, 0), np.int32),
                               features=np.zeros((n, 128), np.float32),
                               n_nodes=int(n)))
    out = []
    while (plan := mb.next_plan()) is not None:
        out.append(([s for _, _, s in plan.spans], plan.batch.n_nodes))
    return out


def test_counts_ignore_padding_and_layout():
    from repro.serve.queue import make_buckets

    padded = _plans(128, make_buckets(512, 1 << 14, levels=3))
    exact = _plans(None, None, tile=8)
    assert [p[0] for p in padded] == [p[0] for p in exact]
    assert [p[1] for p in padded] != [p[1] for p in exact]
    for (sizes, _), (sizes2, _) in zip(padded, exact):
        assert cost.batch_cost(GCN, sizes) == cost.batch_cost(GCN, sizes2)


@pytest.mark.parametrize("jump", ["none", "compact", "sgt"])
def test_counts_ignore_the_jump_mode(jump):
    """The count takes no policy: the same requests served under any
    jump mode cost the same (the program's batches hold the same
    members whatever the policy)."""
    from repro import api

    pol = api.ExecutionPolicy(jump=jump)
    from repro.serve.queue import make_buckets

    sizes = _plans(128, make_buckets(512, 1 << 14, levels=3))
    ref = [cost.batch_cost(GCN, s) for s, _ in sizes]
    with api.use("xla_dot", policy=pol):
        again = _plans(128, make_buckets(512, 1 << 14, levels=3))
    assert [cost.batch_cost(GCN, s) for s, _ in again] == ref


def test_gcn_hand_count():
    # 128 -> 16 -> 16 -> 40, two members of 3 and 5 nodes
    n, sq = 8, 9 + 25
    w = 2 * n * (128 * 16 + 16 * 16 + 16 * 40)
    a = 2 * sq * (16 + 16 + 40)
    assert cost.batch_cost(GCN, [3, 5])[0] == w + a
    assert cost.request_ops(GCN, 3) + cost.request_ops(GCN, 5) == w + a


def test_gin_hand_count():
    # aggregate (d_in), then MLP d_in -> 64 -> d_out per layer
    n, sq = 7, 49
    a = 2 * sq * (128 + 64 + 64)
    w = 2 * n * (128 * 64 + 64 * 64 + 64 * 64 + 64 * 64 + 64 * 64 + 64 * 40)
    assert cost.batch_cost(GIN, [7])[0] == a + w


def test_gcn_bytes_hand_count():
    n = 4
    b = 0
    for d_in, d_out in ((128, 16), (16, 16), (16, 40)):
        b += n * d_in * 8 // 8 + d_in * d_out * 8 // 8 + n * d_out * 4
        b += 16 // 8 + n * d_out * 8 // 8 + n * d_out * 4
    assert cost.batch_cost(GCN, [4])[1] == b


def test_train_step_hand_count():
    cfg = dict(GCN, grad_bits=8)
    sizes, cross = [3, 5], 6
    n, sq = 8, 34
    fwd = bs = 0
    for i, (d_in, d_out) in enumerate(((128, 16), (16, 16), (16, 40))):
        fwd += 2 * n * d_in * d_out + 2 * sq * d_out + 2 * cross * d_out
        bs += (3 if i else 2) * 2 * n * d_in * d_out + 2 * 2 * sq * d_out
    c = cost.train_step_cost(cfg, sizes, cross)
    assert c["model_ops"] == 3 * fwd and c["bitserial_ops"] == bs


def test_share_at_the_roofline_is_at_most_100():
    pk = stats.peaks("TPU v5 lite")
    for sizes in ([113] * 4, [226] * 4, [101, 125]):
        ops, nbytes = cost.batch_cost(GCN, sizes)
        least = max(ops / pk["int8_ops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
        for slower in (1.0, 1.5, 40.0):
            share = 100.0 * least / (least * slower)
            assert 0 < share <= 100.0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        stats.peaks("cpu")
    table = json.loads((ROOT / "bench/peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert table["devices"]["TPU v5 lite"]["int8_ops_per_s"] == 393e12


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95.0
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
