"""Composition of cached tile entries by compiled programs: bit-identity
with the whole-batch build, a compiled set bounded by shapes, donation
that never touches a resident entry, and placement on the members'
device."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import gnn
from repro.perf import spans
from repro.serve import GNNServer, SubgraphRequest, compose_entries
from repro.serve.queue import make_buckets

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ARRAYS = ("adj", "inv_deg", "a_packed", "occupancy", "compact_idx",
          "compact_counts", "sgt_idx", "sgt_counts")
D_IN = 16


@pytest.fixture(scope="module")
def server_args():
    cfg = gnn.GNNConfig.paper_gcn(D_IN, 4)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    return gnn.quantize_params(params, cfg), cfg


def _sparse_adj(rng, n):
    """0/1 adjacency with whole empty tiles, so the remaps' count masks
    and the occupancy maps have zeros to get right."""
    dense = rng.random((n, n)) < 0.05
    keep = rng.random((n // 8, n // 32)) < 0.5
    return (dense & np.kron(keep, np.ones((8, 32), bool))).astype(np.int32)


# member aligned sizes, and which member (if any) lacks the SGT remap
LAYOUTS = {
    "four-128": ([128, 128, 128, 128], None),
    "128-then-256": ([128, 256], None),
    "256-then-128": ([256, 128], None),
    "one-without-sgt": ([128, 256, 128], 1),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_compiled_composition_matches_whole_batch_build(server_args, layout):
    sizes, no_sgt = LAYOUTS[layout]
    srv = GNNServer(*server_args)
    assert srv._align == 128
    rng = np.random.default_rng(sorted(LAYOUTS).index(layout))
    adjs = [_sparse_adj(rng, n) for n in sizes]
    entries = [srv._build_entry(jnp.asarray(a)) for a in adjs]
    if no_sgt is not None:
        entries[no_sgt] = dataclasses.replace(
            entries[no_sgt], sgt_idx=None, sgt_counts=None, sgt_w=0)
    offsets = np.cumsum([0] + sizes[:-1]).tolist()
    n_pad = 512  # leaves padding rows after the shorter layouts
    full = np.zeros((n_pad, n_pad), np.int32)
    for a, off in zip(adjs, offsets):
        full[off:off + len(a), off:off + len(a)] = a
    whole = srv._build_entry(jnp.asarray(full))
    comp = compose_entries(entries, offsets, n_pad, *srv._tile_shape)
    fields = ARRAYS if no_sgt is None else ARRAYS[:-2]
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(comp, f)), np.asarray(getattr(whole, f)),
            err_msg=f"composed {f} != whole-batch {f}")
    assert comp.s_max == whole.s_max
    assert comp.occ_stats == whole.occ_stats
    if no_sgt is None:
        assert comp.sgt_w == whole.sgt_w
    else:  # one member without the remap: the batch carries none
        assert comp.sgt_idx is None and comp.sgt_counts is None
        assert comp.sgt_w == 0


def _request(rng, n):
    e = max(n, 2)
    edges = rng.integers(0, n, size=(2, e)).astype(np.int32)
    feats = rng.standard_normal((n, D_IN)).astype(np.float32)
    return SubgraphRequest(edges=edges, features=feats, n_nodes=n)


def _fresh(r):
    return SubgraphRequest(edges=r.edges, features=r.features,
                           n_nodes=r.n_nodes)


def _serve(srv, members):
    """One step over ``members`` as one batch, in that order; per-request
    logits in member order and the step's ``serve.compose`` record (None
    without a tile cache)."""
    ids = [srv.submit(_fresh(r)) for r in members]
    spans.clear()
    out = srv.step(return_logits=True)
    assert sorted(out) == sorted(ids) and not srv.batcher
    compose = [r for r in spans.recorded() if r.name == "serve.compose"]
    assert len(compose) == (srv.cache is not None)
    return [out[i][1] for i in ids], (compose or [None])[0]


def _snapshot(entries):
    return [{f: np.asarray(getattr(e, f)) for f in ARRAYS
             if getattr(e, f) is not None} for e in entries]


def _unchanged(entries, snap):
    for e, held in zip(entries, snap):
        for f, arr in held.items():
            assert not getattr(e, f).is_deleted(), f"{f} was donated"
            np.testing.assert_array_equal(np.asarray(getattr(e, f)), arr)


def test_shuffled_orders_compile_nothing_and_donate_no_resident(server_args):
    rng = np.random.default_rng(7)
    small = [_request(rng, 100) for _ in range(4)]  # aligned to 128
    large = [_request(rng, 200) for _ in range(2)]  # aligned to 256
    # the single members fill their buckets, so a member buffer has the
    # batch's shape and a wrongly donated one would be reused in place
    sets = [small, [small[0], large[0], small[1]], large,
            [small[2], large[1]], [small[3]], [large[0]]]
    buckets = make_buckets(node_budget=512, edge_budget=4096, levels=3)
    srv = GNNServer(*server_args, buckets=buckets)
    plain = GNNServer(*server_args, buckets=buckets, cache_entries=0)
    for members in sets:  # one warm batch of each size
        _, compose = _serve(srv, members)
        assert compose.attrs == {"composed_hit": 0,
                                 "programs": 1 + len(members)}
    compiles = srv.n_compose_compiles
    assert compiles > 0
    residents = list(srv.cache._entries.values())
    memoized = list(srv._composed.values())
    held_l1, held_l2 = _snapshot(residents), _snapshot(memoized)
    for round_ in range(3):
        for members in sets:
            order = [members[i] for i in rng.permutation(len(members))]
            logits, compose = _serve(srv, order)
            if not compose.attrs["composed_hit"]:
                assert compose.attrs["programs"] == 1 + len(order)
            want, _ = _serve(plain, order)  # the whole-batch build
            for got, ref in zip(logits, want):
                np.testing.assert_array_equal(got, ref)
    assert len(srv._composed) > len(memoized)  # new orders were composed
    assert srv.n_compose_compiles == compiles
    _unchanged(residents, held_l1)
    _unchanged(memoized, held_l2)
    # the same order again is a memo hit: no program dispatched
    _, compose = _serve(srv, sets[0])
    assert compose.attrs == {"composed_hit": 1, "programs": 0}


def test_composed_entry_lives_on_its_replicas_device():
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, {SRC!r})
import jax, numpy as np
from repro.models import gnn
from repro.serve import GNNServer, SubgraphRequest, compose_entries
from repro.serve.queue import make_buckets

cfg = gnn.GNNConfig.paper_gcn({D_IN}, 4)
qp = gnn.quantize_params(gnn.init_params(jax.random.PRNGKey(0), cfg), cfg)
mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
srv = GNNServer(qp, cfg, mesh=mesh,
                buckets=make_buckets(node_budget=512, edge_budget=4096))
rng = np.random.default_rng(0)
for _ in range(8):
    n = 100
    srv.submit(SubgraphRequest(
        edges=rng.integers(0, n, size=(2, n)).astype(np.int32),
        features=rng.standard_normal((n, {D_IN})).astype(np.float32),
        n_nodes=n))
srv.drain()
second = jax.devices()[1]
on_second = 0
for key, entry in srv._composed.items():
    dev = srv._replica_dev[key[-1]]
    for f in ("adj", "inv_deg", "a_packed", "occupancy", "compact_idx",
              "compact_counts", "sgt_idx", "sgt_counts"):
        assert getattr(entry, f).devices() == {{dev}}, (f, dev)
    on_second += dev == second
assert on_second, "no batch was routed to the second device"
# the init program makes its arrays on the members' device: composing the
# second device's cached entries needs no implicit copy from the first
mine = [e for k, e in srv.cache._entries.items()
        if srv._replica_dev[k[-1]] == second][:2]
with jax.transfer_guard_device_to_device("disallow"):
    comp = compose_entries(mine, [0, 128], 256, *srv._tile_shape)
assert comp.adj.devices() == {{second}}
print("OK", on_second)
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    assert "OK" in out.stdout
