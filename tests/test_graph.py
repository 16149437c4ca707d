"""Graph substrate: partitioner invariants, CSR, batching, packed transfer."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitops
from repro.graph import batching, datasets, packing, partition
from repro.graph.sparse import CSR, edges_to_csr, sparse_to_dense

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")


@st.composite
def random_graph(draw):
    n = draw(st.integers(8, 200))
    e = draw(st.integers(n, 4 * n))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, (2, e))
    return edges_to_csr(edges, n), draw(st.integers(2, 8))


@given(random_graph())
def test_partition_invariants(gk):
    csr, k = gk
    parts = partition.partition(csr, k)
    # every node assigned exactly once, to a valid part
    assert parts.shape == (csr.n,)
    assert parts.min() >= 0 and parts.max() < k
    # balance within tolerance: +-10% cap plus one node of integer slack
    # (tiny graphs with k ~ n cannot balance below ceil granularity)
    sizes = np.bincount(parts, minlength=k)
    assert sizes.max() <= int(np.ceil(csr.n / k * 1.1)) + 1


@given(st.integers(0, 2**31 - 1))
def test_partition_beats_random_edge_cut(seed):
    data = datasets.make_sbm_graph(400, 2400, 8, 4, seed=seed)
    k = 8
    ours = partition.edge_cut(data.csr, partition.partition(data.csr, k))
    rand = partition.edge_cut(data.csr,
                              partition.random_partition(data.csr.n, k, seed))
    assert ours <= rand  # community structure must be exploited


def test_csr_roundtrip_and_subgraph():
    edges = np.array([[0, 1, 2, 3], [1, 2, 3, 0]])
    csr = edges_to_csr(edges, 5)
    el = csr.edge_list()
    assert el.shape[0] == 2
    sub = csr.subgraph(np.array([0, 1, 2]))
    assert sub.n == 3
    # symmetrized: 0-1, 1-2 survive; edges to 3 dropped
    assert sub.e == 4


def test_sparse_to_dense_with_padding():
    edges = jnp.asarray([[0, 2, -1], [1, 0, -1]], jnp.int32)
    a = sparse_to_dense(edges, 4)
    want = np.zeros((4, 4), np.int32)
    want[0, 1] = want[2, 0] = 1
    np.testing.assert_array_equal(np.asarray(a), want)


def test_batching_block_diagonal():
    data = datasets.load("proteins", scale=0.02, seed=1)
    parts = partition.partition(data.csr, 8)
    bs = batching.make_batches(data, parts, batch_size=2, tile=64)
    total_valid = sum(b.n_valid for b in bs)
    assert total_valid == data.csr.n
    for b in bs:
        assert b.n_nodes % 64 == 0
        e = b.edges
        valid = e[0] >= 0
        assert (e[:, valid] < b.n_valid).all()


def test_packed_transfer_matches_dense():
    """Strategy III (compound packed) reproduces strategy I tensors."""
    data = datasets.load("proteins", scale=0.02, seed=2)
    parts = partition.partition(data.csr, 4)
    b = batching.make_batches(data, parts, batch_size=2, tile=64)[0]
    adj_d, feats_d = packing.transfer_dense(b)
    adj_p, packed, meta = packing.transfer_packed(b, nbits=8)
    np.testing.assert_array_equal(np.asarray(adj_p), np.asarray(adj_d))
    # features decode to the 8-bit quantization of the dense features
    from repro.core import bitops
    xq = bitops.bit_compose(bitops.unpack_along_axis(packed, axis=2,
                                                     size=meta["d"]))
    x = np.asarray(xq, np.float32) * meta["scale"] + meta["zero"]
    err = np.abs(x - np.asarray(feats_d))
    assert err.max() <= meta["scale"] * 1.001


def test_packed_transfer_byte_accounting():
    data = datasets.load("proteins", scale=0.02, seed=3)
    parts = partition.partition(data.csr, 4)
    b = batching.make_batches(data, parts, batch_size=2, tile=64)[0]
    nb = packing.compound_nbytes(b, nbits=8)
    assert nb["III_packed"] < nb["II_sparse"] < nb["I_dense"]


def _synthetic_batch(d, kind, seed=0, n_nodes=64, n_valid=50, e_cap=96):
    """A padded batch: rows past ``n_valid`` zero, edges -1 padded."""
    rng = np.random.default_rng(seed)
    feats = np.zeros((n_nodes, d), np.float32)
    if kind == "constant":  # fmin == fmax: the 1e-8 scale floor
        n_valid = n_nodes
        feats[:] = 0.7
    else:
        feats[:n_valid] = rng.standard_normal((n_valid, d)) * 3 + 1
    n_edges = 0 if kind == "no_edges" else e_cap - 9
    edges = np.full((2, 0 if kind == "no_edges" else e_cap), -1, np.int32)
    edges[:, :n_edges] = rng.integers(0, n_valid, (2, n_edges))
    return batching.SubgraphBatch(
        edges=edges, n_nodes=n_nodes, n_valid=n_valid, features=feats,
        labels=np.full(n_nodes, -1, np.int32),
        train_mask=np.zeros(n_nodes, bool),
        node_ids=np.full(n_nodes, -1, np.int64), n_edges=n_edges)


def _reference_pack(b, nbits, with_edges):
    """The compound layout as a uint32 plane stack and np_pack_words."""
    f = b.features
    fmin, fmax = float(f.min()), float(f.max())
    scale = max((fmax - fmin) / (1 << nbits), 1e-8)
    q = np.clip(np.floor((f - fmin) / scale), 0, (1 << nbits) - 1)
    q = q.astype(np.uint32)
    planes = np.stack([(q >> i) & 1 for i in range(nbits)])
    packed = bitops.np_pack_words(planes)
    e_cap = b.edges.shape[1] if with_edges else 0
    n, d = q.shape
    header = np.array([b.n_nodes, b.n_valid, b.n_edges, d, nbits, e_cap,
                       packed.shape[-1], 0], dtype=np.uint32)
    parts = [header, packed.ravel()]
    if with_edges:
        parts.insert(1, b.edges.astype(np.int32).view(np.uint32).ravel())
    meta = {"scale": scale, "zero": fmin, "n": n, "d": d, "nbits": nbits,
            "e_cap": e_cap, "wpf": packed.shape[-1]}
    return np.concatenate(parts), meta


_PACK_CASES = (
    [(nbits, d, "random") for nbits in (*range(1, 9), 9, 16)
     for d in (128, 100, 32, 5)]
    + [(8, 128, "constant"), (3, 100, "constant"), (8, 128, "no_edges"),
       (9, 5, "no_edges")])


@pytest.mark.parametrize("nbits,d,kind", _PACK_CASES)
def test_pack_matches_plane_stack_reference(nbits, d, kind):
    """pack_compound / pack_feats are byte-identical to the plane stack."""
    b = _synthetic_batch(d, kind, seed=nbits * 1000 + d)
    for fn, with_edges in ((packing.pack_compound, True),
                           (packing.pack_feats, False)):
        buf, meta = fn(b, nbits=nbits)
        want, want_meta = _reference_pack(b, nbits, with_edges)
        assert buf.dtype == np.uint32 and buf.shape == want.shape
        assert buf.tobytes() == want.tobytes()
        assert meta == want_meta
    nb = packing.compound_nbytes(b, nbits=nbits)
    assert nb["III_packed"] == packing.pack_compound(b, nbits)[0].nbytes
    assert nb["III_feats"] == packing.pack_feats(b, nbits)[0].nbytes


def test_pack_compound_roundtrip_matches_dense():
    """A 9-bit, D=100 compound buffer unpacks to strategy I's tensors."""
    b = _synthetic_batch(100, "random", seed=7)
    adj_d, feats_d = packing.transfer_dense(b)
    buf, meta = packing.pack_compound(b, nbits=9)
    adj_p, packed = packing.unpack_compound(
        jnp.asarray(buf), n=meta["n"], d=meta["d"], nbits=meta["nbits"],
        e_cap=meta["e_cap"], wpf=meta["wpf"])
    np.testing.assert_array_equal(np.asarray(adj_p), np.asarray(adj_d))
    xq = bitops.bit_compose(bitops.unpack_along_axis(packed, axis=2,
                                                     size=meta["d"]))
    x = np.asarray(xq, np.float32) * meta["scale"] + meta["zero"]
    err = np.abs(x - np.asarray(feats_d))
    assert err.max() <= meta["scale"] * 1.001
