"""Admission control/backpressure + per-subgraph tile-cache composition."""
import jax
import numpy as np
import pytest

from repro.graph import datasets, partition
from repro.models import gnn
from repro.serve import (AdmissionError, AdmissionPolicy, GNNServer,
                         MicroBatcher, SubgraphRequest, compose_entries,
                         make_buckets, requests_from_partitions)
from repro.serve.queue import buckets_for


@pytest.fixture(scope="module")
def setup():
    data = datasets.load("ogbn-arxiv", scale=0.008, seed=0)
    parts = partition.partition(data.csr, 8)
    cfg = gnn.GNNConfig.paper_gcn(data.features.shape[1], data.n_classes)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    qparams = gnn.quantize_params(params, cfg)
    reqs = requests_from_partitions(data, parts)
    return cfg, qparams, reqs


def _fresh(r, **kw):
    return SubgraphRequest(edges=r.edges, features=r.features,
                           n_nodes=r.n_nodes, **kw)


# ----------------------------------------------------------- policy object

def test_admission_policy_validation():
    with pytest.raises(ValueError, match="on_full"):
        AdmissionPolicy(on_full="drop")
    with pytest.raises(ValueError, match="max_depth must be positive"):
        AdmissionPolicy(max_depth=0)
    with pytest.raises(ValueError, match="per_client_share"):
        AdmissionPolicy(max_depth=4, per_client_share=1.5)
    with pytest.raises(ValueError, match="needs max_depth"):
        AdmissionPolicy(per_client_share=0.5)
    assert AdmissionPolicy(max_depth=10, per_client_share=0.25).client_cap == 3
    assert AdmissionPolicy(max_depth=10).client_cap is None


def test_batcher_bounds_depth_nodes_edges(setup):
    _, _, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    pol = AdmissionPolicy(max_depth=2)
    mb = MicroBatcher(buckets, admission=pol)
    mb.add(_fresh(reqs[0]))
    mb.add(_fresh(reqs[1]))
    assert mb.admit_reason(_fresh(reqs[2])) is not None
    with pytest.raises(AdmissionError, match="max_depth=2"):
        mb.add(_fresh(reqs[2]))
    # draining a plan frees the slots (and the node/edge accounting)
    mb.next_plan()
    assert mb.queued_nodes == 0 and mb.queued_edges == 0
    assert mb.admit_reason(_fresh(reqs[2])) is None

    cap_n = reqs[0].n_nodes + 1
    mb2 = MicroBatcher(buckets, admission=AdmissionPolicy(max_nodes=cap_n))
    mb2.add(_fresh(reqs[0]))
    with pytest.raises(AdmissionError, match="max_nodes"):
        mb2.add(_fresh(reqs[1]))
    mb3 = MicroBatcher(buckets,
                       admission=AdmissionPolicy(max_edges=reqs[0].n_edges))
    mb3.add(_fresh(reqs[0]))
    with pytest.raises(AdmissionError, match="max_edges"):
        mb3.add(_fresh(reqs[1]))


def test_per_client_fair_share(setup):
    _, _, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    pol = AdmissionPolicy(max_depth=8, per_client_share=0.25)  # cap 2/client
    mb = MicroBatcher(buckets, admission=pol)
    mb.add(_fresh(reqs[0], client_id="flood"))
    mb.add(_fresh(reqs[1], client_id="flood"))
    with pytest.raises(AdmissionError, match="fair-share"):
        mb.add(_fresh(reqs[2], client_id="flood"))
    # other clients and anonymous requests are unaffected
    mb.add(_fresh(reqs[2], client_id="other"))
    mb.add(_fresh(reqs[3]))
    # serving the flood's requests frees its share
    while mb.next_plan() is not None:
        pass
    mb.add(_fresh(reqs[4], client_id="flood"))


def test_oversized_request_still_config_error(setup):
    """Budget violations are misconfiguration (ValueError), not shed load."""
    _, _, reqs = setup
    mb = MicroBatcher(make_buckets(node_budget=128, edge_budget=64),
                      admission=AdmissionPolicy(max_depth=100))
    with pytest.raises(ValueError, match="exceeds the batch budget"):
        mb.add(_fresh(reqs[0]))


# ------------------------------------------------------------ engine: reject

def test_reject_mode_sheds_with_reason_and_monotone_stats(setup):
    cfg, qparams, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    srv = GNNServer(qparams, cfg, buckets=buckets,
                    admission=AdmissionPolicy(max_depth=3))
    submits, served = 0, {}
    for wave in range(2):
        ids = [srv.submit(_fresh(r)) for r in reqs]
        submits += len(ids)
        shed_wave = sum(i is None for i in ids)
        assert shed_wave == len(reqs) - 3  # bounded queue: depth 3 admitted
        served.update(srv.drain())
    st = srv.stats
    assert st.requests_shed == 2 * (len(reqs) - 3)
    assert st.requests_admitted == 6
    # monotonicity: every submit is admitted xor shed, and every admitted
    # request is eventually served
    assert st.requests_admitted + st.requests_shed == submits
    assert len(served) == st.requests_admitted == st.requests
    assert st.shed_reasons == {"queue depth at max_depth=3": st.requests_shed}
    s = st.summary()
    assert s["requests_shed"] == st.requests_shed
    assert s["queue_n"] == st.requests  # queue-wait recorded per served req


# ------------------------------------------------------------- engine: block

def test_block_mode_backpressure_serves_everything(setup):
    cfg, qparams, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    srv = GNNServer(qparams, cfg, buckets=buckets,
                    admission=AdmissionPolicy(max_depth=2, on_full="block"))
    ids = [srv.submit(_fresh(r)) for r in reqs]
    assert all(i is not None for i in ids)  # nothing shed
    out = srv.drain()
    assert set(out) == set(ids)  # blocked-submit results are not lost
    st = srv.stats
    assert st.requests_shed == 0
    assert st.submit_blocked > 0  # backpressure actually engaged
    assert st.requests == len(reqs)


def test_block_mode_impossible_request_raises(setup):
    cfg, qparams, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    srv = GNNServer(qparams, cfg, buckets=buckets,
                    admission=AdmissionPolicy(max_nodes=1, on_full="block"))
    with pytest.raises(ValueError, match="can never be admitted"):
        srv.submit(_fresh(reqs[0]))


# --------------------------------------- per-subgraph cache composition

def test_shuffled_coalescing_order_hits_and_is_bit_identical(setup):
    """A repeat subgraph must hit the cache in ANY coalescing order, and
    the composed batch artifacts must produce logits bit-identical to a
    cache-disabled server building everything from scratch on the same
    traffic."""
    cfg, qparams, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    warm = GNNServer(qparams, cfg, buckets=buckets)
    for r in reqs:  # cold wave, original order
        warm.submit(_fresh(r))
    warm.drain()
    hits0, misses0 = warm.cache.hits, warm.cache.misses
    st = warm.stats
    assert st.cache_misses - st.cache_partial_hits > 0 and st.cache_hits == 0
    cold_batches = st.batches

    rng = np.random.default_rng(3)
    for rnd in range(2):
        order = rng.permutation(len(reqs))
        ref = GNNServer(qparams, cfg, buckets=buckets, cache_entries=0)
        pairs = []
        for i in order:
            wid = warm.submit(_fresh(reqs[i]))
            rid = ref.submit(_fresh(reqs[i]))
            pairs.append((wid, rid))
        got_w = warm.drain(return_logits=True)
        got_r = ref.drain(return_logits=True)
        for wid, rid in pairs:
            pw, lw = got_w[wid]
            pr, lr = got_r[rid]
            np.testing.assert_array_equal(lw, lr)  # bit-identical
            np.testing.assert_array_equal(pw, pr)
    # per-key: every shuffled-round lookup hit (100% ≥ the 90% bar)
    assert warm.cache.misses == misses0
    assert warm.cache.hits == hits0 + 2 * len(reqs)
    # batch-level: every shuffled batch was a FULL hit (features-only
    # transfer), even though the groupings never matched the cold wave's
    assert st.cache_partial_hits == 0 and st.cache_misses == cold_batches
    assert st.cache_hits == st.batches - cold_batches > 0


def test_partial_composition_hit_accounting(setup):
    """A batch with SOME members cached is a partial hit, never a full one
    — it still ships the compound buffer, so counting it as a hit would
    overstate the transfer savings."""
    cfg, qparams, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    srv = GNNServer(qparams, cfg, buckets=buckets,
                    node_budget=buckets[-1].n_pad)
    # warm exactly one subgraph (alone in its batch)
    srv.submit(_fresh(reqs[0]))
    srv.drain()
    st = srv.stats
    assert (st.cache_misses - st.cache_partial_hits, st.cache_partial_hits,
            st.cache_hits) == (1, 0, 0)
    # now coalesce it with an unseen subgraph -> partial composition hit
    srv.submit(_fresh(reqs[0]))
    srv.submit(_fresh(reqs[1]))
    out = srv.drain()
    assert len(out) == 2
    assert st.cache_partial_hits == 1
    assert st.cache_hits == 0  # partial is NOT a (transfer) hit
    # repeat the same pair -> now a full hit
    srv.submit(_fresh(reqs[0]))
    srv.submit(_fresh(reqs[1]))
    srv.drain()
    assert st.cache_hits == 1
    assert st.cache_hits / (st.cache_hits + st.cache_misses) == \
        pytest.approx(1 / 3)


def test_compose_entries_matches_whole_batch_build(setup):
    """Composed artifacts are bit-identical to building from the full
    block-diagonal adjacency — the invariant the serving fast path rests
    on."""
    from repro.graph.packing import transfer_packed

    cfg, qparams, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    srv = GNNServer(qparams, cfg, buckets=buckets)
    for r in reqs[:4]:
        srv.submit(_fresh(r))
    plan = srv.batcher.next_plan()
    assert len(plan.requests) >= 2  # composition must actually compose
    adj, _, _ = transfer_packed(plan.batch, nbits=8)
    whole = srv._build_entry(adj)
    subs, offs = [], []
    for _, off, n in plan.spans:
        n_pad = -(-n // srv._align) * srv._align
        subs.append(srv._build_entry(
            jax.lax.dynamic_slice(adj, (off, off), (n_pad, n_pad))))
        offs.append(off)
    comp = compose_entries(subs, offs, plan.batch.n_nodes, *srv._tile_shape)
    for f in ("adj", "inv_deg", "a_packed", "occupancy", "compact_idx",
              "compact_counts"):
        np.testing.assert_array_equal(
            np.asarray(getattr(comp, f)), np.asarray(getattr(whole, f)),
            err_msg=f"composed {f} != whole-batch {f}")
    assert comp.s_max == whole.s_max
    assert comp.occ_stats == whole.occ_stats


def test_compose_entries_rejects_misaligned_offsets(setup):
    cfg, qparams, reqs = setup
    srv = GNNServer(qparams, cfg)
    e = srv._build_entry(jax.numpy.zeros((128, 128), jax.numpy.int32))
    with pytest.raises(ValueError, match="not tile-aligned"):
        compose_entries([e], [64], 256, *srv._tile_shape)
    with pytest.raises(ValueError, match="not a multiple of the tile grid"):
        compose_entries([e], [0], 130, *srv._tile_shape)


def test_mismatched_ambient_grid_drops_cached_tiles(setup):
    """Cached compact tiles live on the construction-time tile grid; an
    ambient policy with a different grid must not consume them (the
    kernel would jump on the wrong tiles) — jumping degrades to in-call
    recompute instead of corrupting results."""
    from repro import api

    cfg, qparams, _ = setup
    srv = GNNServer(qparams, cfg, backend="pallas")
    entry = srv._build_entry(jax.numpy.eye(128, dtype=jax.numpy.int32))
    with api.use("pallas", policy=api.ExecutionPolicy(jump="compact",
                                                      block_m=16)):
        assert srv._jump_tiles(entry) == (None, None, 0, None)
    with api.use("pallas", policy=api.ExecutionPolicy(jump="compact")):
        assert srv._jump_tiles(entry)[0] is not None


@pytest.mark.parametrize("grid_from", ["policy", "ambient"])
def test_misaligned_buckets_fail_at_construction(setup, grid_from):
    """The tile grid comes from ``policy=`` or, without one, from the
    ambient ``repro.api`` policy; either way a grid the bucket ladder is
    not aligned to fails at construction."""
    cfg, qparams, reqs = setup
    from repro import api

    buckets = buckets_for(reqs, levels=2)
    pol = api.ExecutionPolicy(block_w=8)
    with pytest.raises(ValueError, match="tile"):
        if grid_from == "policy":
            GNNServer(qparams, cfg, policy=pol, buckets=buckets)
        else:
            with api.use(policy=pol):
                GNNServer(qparams, cfg, buckets=buckets)


def test_routing_fingerprint_is_order_insensitive(setup):
    """Replica routing must not depend on the coalescing order, or a
    reordered repeat group would land on a replica without its tiles."""
    _, _, reqs = setup
    buckets = buckets_for(reqs, levels=2)
    mb1 = MicroBatcher(buckets, align=128)
    mb2 = MicroBatcher(buckets, align=128)
    for r in reqs[:3]:
        mb1.add(_fresh(r))
    for r in (reqs[2], reqs[0], reqs[1]):
        mb2.add(_fresh(r))
    p1, p2 = mb1.next_plan(), mb2.next_plan()
    assert [r.fingerprint for r in p1.requests] != \
        [r.fingerprint for r in p2.requests]
    assert p1.fingerprint == p2.fingerprint
