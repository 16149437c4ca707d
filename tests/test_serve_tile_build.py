"""The tile-entry build of missed members as compiled programs: entries
equal to a numpy oracle bit for bit, one host fetch a step, a compiled
set bounded by shapes (never by offsets), and cold logits equal to the
cache-less whole-batch build."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DEFAULT_POLICY
from repro.models import gnn
from repro.perf import spans
from repro.serve import GNNServer, SubgraphRequest, cache
from repro.serve.cache import build_entries
from repro.serve.queue import make_buckets

TM, TW = DEFAULT_POLICY.block_m, DEFAULT_POLICY.block_w
N_BATCH = 1024
D_IN = 16
ARRAYS = ("adj", "inv_deg", "a_packed", "occupancy", "compact_idx",
          "compact_counts", "sgt_idx", "sgt_counts")


def _pad(a, axis, multiple):
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, -a.shape[axis] % multiple)
    return np.pad(a, pads)


def _remap(occ):
    """Per row, the ascending ids of its occupied columns, tail 0."""
    idx = np.zeros(occ.shape, np.int32)
    counts = occ.sum(axis=1).astype(np.int32)
    for i, row in enumerate(occ):
        nz = np.flatnonzero(row)
        idx[i, :len(nz)] = nz
    return idx, counts


def _oracle(sub):
    """Every field of a member's tile entry, from its 0/1 block alone."""
    n = len(sub)
    deg = sub.sum(axis=1, keepdims=True).astype(np.float32)
    inv_deg = np.float32(1.0) / (deg + np.float32(1.0))
    bits = _pad(sub, 1, 32).astype(np.uint32).reshape(n, -1, 32)
    words = (bits << np.arange(32, dtype=np.uint32)).sum(axis=2,
                                                        dtype=np.uint32)
    ap = _pad(_pad(words, 0, TM), 1, TW)
    mt, wt = ap.shape[0] // TM, ap.shape[1]
    occ = (ap.reshape(mt, TM, wt // TW, TW) != 0).any(axis=(1, 3))
    word_occ = (ap.reshape(mt, TM, wt) != 0).any(axis=1)
    idx, counts = _remap(occ)
    s_idx, s_counts = _remap(word_occ)
    nz = int(occ.sum())
    return {
        "adj": sub, "inv_deg": inv_deg, "a_packed": ap,
        "occupancy": occ.astype(np.int32), "compact_idx": idx,
        "compact_counts": counts, "sgt_idx": s_idx, "sgt_counts": s_counts,
        "occ_stats": {"tiles_total": occ.size, "tiles_nonzero": nz,
                      "tiles_zero": occ.size - nz,
                      "nonzero_ratio": nz / occ.size,
                      "skip_ratio": 1.0 - nz / occ.size},
        "s_max": int(counts.max()), "sgt_w": int(s_counts.max()),
    }


def _block(kind, n, rng):
    if kind == "empty":
        return np.zeros((n, n), np.int32)
    if kind == "identity":
        return np.eye(n, dtype=np.int32)
    if kind == "dense":
        return np.ones((n, n), np.int32)
    # sparse, with whole empty tiles and word columns
    keep = rng.random((n // TM, n // 32)) < 0.4
    return ((rng.random((n, n)) < 0.05)
            & np.kron(keep, np.ones((TM, 32), bool))).astype(np.int32)


@pytest.mark.parametrize("n_sub", [128, 256, 384])
@pytest.mark.parametrize("kind", ["empty", "identity", "sparse", "dense"])
def test_built_entry_equals_numpy_oracle(kind, n_sub):
    rng = np.random.default_rng(n_sub + len(kind))
    # noise everywhere else: a slice at the wrong offset reads it
    batch = (rng.random((N_BATCH, N_BATCH)) < 0.3).astype(np.int32)
    offsets = [128, N_BATCH - n_sub]
    for off in offsets:
        batch[off:off + n_sub, off:off + n_sub] = _block(kind, n_sub, rng)
    entries = build_entries(jnp.asarray(batch),
                            [(off, n_sub) for off in offsets], TM, TW)
    for off, entry in zip(offsets, entries):
        want = _oracle(batch[off:off + n_sub, off:off + n_sub])
        for f in ARRAYS:
            got = np.asarray(getattr(entry, f))
            assert got.dtype == want[f].dtype, f
            np.testing.assert_array_equal(got, want[f], err_msg=f)
        for f in ("occ_stats", "s_max", "sgt_w"):
            assert getattr(entry, f) == want[f], f


def test_a_member_outside_the_batch_is_refused():
    adj = jnp.zeros((256, 256), jnp.int32)
    with pytest.raises(ValueError, match="does not fit"):
        build_entries(adj, [(128, 256)], TM, TW)


@pytest.fixture(scope="module")
def server_args():
    cfg = gnn.GNNConfig.paper_gcn(D_IN, 4)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    return gnn.quantize_params(params, cfg), cfg


def _request(rng, n):
    edges = rng.integers(0, n, size=(2, 3 * n)).astype(np.int32)
    feats = rng.standard_normal((n, D_IN)).astype(np.float32)
    return SubgraphRequest(edges=edges, features=feats, n_nodes=n)


def _fresh(r):
    return SubgraphRequest(edges=r.edges, features=r.features,
                           n_nodes=r.n_nodes)


def _server(server_args, **kw):
    buckets = make_buckets(node_budget=512, edge_budget=4096, levels=3)
    return GNNServer(*server_args, buckets=buckets, **kw)


def _step(srv, members):
    """One step over ``members`` as one batch; per-request logits in member
    order and the step's ``serve.tile_build`` records."""
    ids = [srv.submit(_fresh(r)) for r in members]
    spans.clear()
    out = srv.step(return_logits=True)
    assert sorted(out) == sorted(ids) and not srv.batcher
    builds = [r for r in spans.recorded() if r.name == "serve.tile_build"]
    return [out[i][1] for i in ids], builds


def test_a_cold_step_fetches_once_and_records_its_programs(server_args,
                                                           monkeypatch):
    rng = np.random.default_rng(1)
    members = [_request(rng, 100) for _ in range(4)]
    srv = _server(server_args)
    _step(srv, [_request(rng, 100) for _ in range(4)])  # compiles the shapes
    fetches, ints = [], []
    device_get, to_int = jax.device_get, type(jnp.zeros(())).__int__

    def counting_get(x):
        fetches.append(x)
        return device_get(x)

    def counting_int(self):
        ints.append(self)
        return to_int(self)

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(type(jnp.zeros(())), "__int__", counting_int)
    misses = srv.cache.misses
    _, builds = _step(srv, members)
    assert srv.cache.misses == misses + 4
    build, = builds
    assert build.attrs == {"programs": 4}
    # one fetch of the four stats vectors, no per-member sync
    assert len(fetches) == 1 and len(fetches[0]) == 4
    assert ints == []


def test_seen_sizes_at_new_offsets_compile_nothing(server_args):
    rng = np.random.default_rng(2)
    small = [_request(rng, 100) for _ in range(6)]  # aligned to 128
    large = [_request(rng, 200) for _ in range(3)]  # aligned to 256
    srv = _server(server_args)
    cache._build.clear_cache()  # the programs are shared by the process
    _step(srv, small[:2] + large[:1])  # 128 at 0 and 128, 256 at 256
    compiles = srv.n_build_compiles
    assert compiles == 2  # one program per aligned member size
    # the same sizes in the same bucket, at offsets not built at before
    for members in ([large[1]] + small[2:4], [small[4], large[2], small[5]]):
        _, builds = _step(srv, members)
        assert builds[0].attrs == {"programs": 3}
    assert srv.n_build_compiles == compiles
    # a new aligned size does compile: the counter sees the build
    _step(srv, [_request(rng, 300)])
    assert srv.n_build_compiles == compiles + 1


def test_cold_and_partial_plans_match_the_whole_batch_build(server_args):
    rng = np.random.default_rng(3)
    reqs = [_request(rng, n) for n in (100, 200, 60, 120, 90)]
    srv = _server(server_args)
    plain = _server(server_args, cache_entries=0)  # cache-less _execute
    for members in (reqs[:3], [reqs[3], reqs[0], reqs[4]]):
        logits, builds = _step(srv, members)
        assert builds  # every plan here misses a member
        want, none = _step(plain, members)
        assert none == []
        for got, ref in zip(logits, want):
            np.testing.assert_array_equal(got, ref)
