"""The program's span recorder (repro.perf.spans) and the spans of the
serving step."""
import threading
import time

import jax
import pytest

from repro.graph import datasets, partition
from repro.models import gnn
from repro.perf import spans
from repro.serve import GNNServer, SubgraphRequest, requests_from_partitions
from repro.serve.queue import buckets_for

# one record per phase of a step, each a direct child of serve.step (the
# graph layer's pack and transfer spans take the step as their parent)
PHASES = ("serve.coalesce", "serve.compose", "pack", "transfer",
          "serve.dispatch", "serve.device_wait", "serve.readback")


@pytest.fixture(autouse=True)
def empty_ring():
    spans.clear()
    yield
    spans.clear()


def test_nesting_sets_parent_ids_and_attrs_set_in_the_body():
    with spans.span("outer", a=1) as outer:
        with spans.span("inner") as inner:
            inner["n"] = 3
        with spans.span("sibling"):
            pass
        outer["b"] = 2
    inner_r, sib_r, outer_r = spans.recorded()  # in the order they closed
    assert [r.name for r in (inner_r, sib_r, outer_r)] == \
        ["inner", "sibling", "outer"]
    assert outer_r.parent_id is None
    assert inner_r.parent_id == sib_r.parent_id == outer_r.id
    assert len({inner_r.id, sib_r.id, outer_r.id}) == 3
    assert inner_r.attrs == {"n": 3} and outer_r.attrs == {"a": 1, "b": 2}
    assert outer_r.t0 <= inner_r.t0 <= inner_r.t1 <= sib_r.t0 \
        <= sib_r.t1 <= outer_r.t1


def test_a_span_closed_by_an_exception_is_recorded_and_unwinds():
    with pytest.raises(RuntimeError):
        with spans.span("outer"):
            with spans.span("fails"):
                raise RuntimeError("boom")
    with spans.span("after"):
        pass
    fails, outer, after = spans.recorded()
    assert fails.parent_id == outer.id and after.parent_id is None


def test_open_spans_are_per_thread():
    seen = {}

    def other():
        with spans.span("other"):
            pass
        seen["done"] = True

    with spans.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive() and seen["done"]
    rec = {r.name: r for r in spans.recorded()}
    assert rec["other"].parent_id is None


def test_records_hold_perf_counter_stamps_only():
    before = time.perf_counter()
    with spans.span("s"):
        pass
    after = time.perf_counter()
    r, = spans.recorded()
    assert tuple(r._fields) == ("id", "parent_id", "name", "t0", "t1",
                                "attrs")
    assert before <= r.t0 <= r.t1 <= after


def test_the_ring_keeps_the_latest_records():
    for i in range(spans.RING + 10):
        with spans.span("s", i=i):
            pass
    held = spans.recorded()
    assert len(held) == spans.RING
    assert held[0].attrs["i"] == 10 and held[-1].attrs["i"] == spans.RING + 9
    spans.clear()
    assert spans.recorded() == []


def test_a_span_reaches_the_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("traced"):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path, = tmp_path.glob("**/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert spans.PREFIX + "traced" in names


# ------------------------------------------------------- the serving step

@pytest.fixture(scope="module")
def served():
    data = datasets.load("ogbn-arxiv", scale=0.008, seed=0)
    parts = partition.partition(data.csr, 8)
    cfg = gnn.GNNConfig.paper_gcn(data.features.shape[1], data.n_classes)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    reqs = requests_from_partitions(data, parts)
    srv = GNNServer(gnn.quantize_params(params, cfg), cfg,
                    buckets=buckets_for(reqs, levels=2))
    return srv, reqs


def _fresh(r):
    return SubgraphRequest(edges=r.edges, features=r.features,
                           n_nodes=r.n_nodes)


def _one_step(srv, reqs):
    """Submit ``reqs`` as one batch and run one step; its step record and
    the records under it, by name."""
    for r in reqs:
        srv.submit(_fresh(r))
    spans.clear()
    out = srv.step()
    assert len(out) == len(reqs) and not srv.batcher
    held = spans.recorded()
    step, = [r for r in held if r.name == "serve.step"]
    kids = [r for r in held if r.parent_id == step.id]
    assert {r.id for r in kids} | {step.id} == {r.id for r in held}
    by_name = {}
    for r in kids:
        by_name.setdefault(r.name, []).append(r)
    return step, by_name


def test_a_cached_batch_records_each_phase_once(served):
    srv, reqs = served
    pair = reqs[:2]
    _one_step(srv, pair)  # caches both members
    step, phases = _one_step(srv, pair)
    assert sorted(phases) == sorted(PHASES)
    assert all(len(v) == 1 for v in phases.values())
    compose, = phases["serve.compose"]
    # the same order again: a memo hit dispatches no program
    assert compose.attrs == {"composed_hit": 1, "programs": 0}
    for (r,) in phases.values():
        assert step.t0 <= r.t0 <= r.t1 <= step.t1


def test_a_batch_with_misses_records_the_tile_build(served):
    srv, reqs = served
    _one_step(srv, reqs[2:3])  # caches one member
    misses = srv.cache.misses
    _, phases = _one_step(srv, reqs[2:4])  # that one and an unseen one
    assert sorted(phases) == sorted(PHASES + ("serve.tile_build",))
    build, = phases["serve.tile_build"]
    assert srv.cache.misses == misses + 1  # the one member it built
    # one build program for the one missed member
    assert build.attrs == {"programs": 1}
    compose, = phases["serve.compose"]
    # a miss: the init program, then one placement per member
    assert compose.attrs == {"composed_hit": 0, "programs": 3}
