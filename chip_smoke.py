"""Smoke run of the QGTC main path on a TPU, at full scale.

Drives the system once through the APIs its launchers wrap, in one
process, and checks the answers by the repository's exactness invariant
(every backend returns the same integers):

  serve  - ``repro.serve.GNNServer`` (the engine behind
           ``python -m repro.launch.serve --gnn``) answers the 1500
           Cluster-GCN requests of full-size ogbn-arxiv (169,343 nodes,
           128 features) twice, cold and then cache-warm, with the paper's
           8-bit GCN on the Pallas kernels under the jump modes none,
           compact and sgt. Its logits must equal those of
           ``backend="xla_dot"`` bit for bit, and the jitted forward must
           hold a Pallas TPU kernel (``tpu_custom_call``).
  train  - five steps of the integer training path
           (``repro.train.trainer``, as ``python -m repro.launch.train
           --int-path``) on the same graph with ``backend="pallas"`` and
           8-bit integer gradients, for the paper's GCN and for its GIN
           (3 layers, hidden 64); the losses must be finite and equal to
           the same steps on ``xla_dot``.

``--chips 4`` runs only the replica phase: four serving replicas, one per
chip, against one replica on one chip; every request's logits must be
bit-identical and every chip must have run batches.

The numbers printed are smoke numbers (one run, compiles included), not
benchmark numbers. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase raises and exits non-zero, and without a TPU the script
exits non-zero before any phase.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the replica phase on four chips
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import warnings

import numpy as np

DATASET = "ogbn-arxiv"
PARTS = 1500        # the paper's Cluster-GCN part count for this graph
FEAT_BITS = 8
JUMPS = ("none", "compact", "sgt")
TRAIN_STEPS = 5
TRAIN_ARCHS = ("qgtc-gcn", "qgtc-gin")


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _fresh(req):
    """A new request for the same subgraph (a new id, nothing shared)."""
    return type(req)(edges=req.edges, features=req.features,
                     n_nodes=req.n_nodes)


def _serve_round(server, reqs) -> list:
    """Submit every request once, drain; logits in request order."""
    ids = [server.submit(_fresh(r)) for r in reqs]
    if any(i is None for i in ids):
        raise RuntimeError("the server shed a request with no admission "
                           "policy set")
    out = server.drain(return_logits=True)
    return [out[i][1] for i in ids]


def _check_equal(name: str, got: list, ref: list) -> None:
    bad = [k for k, (g, r) in enumerate(zip(got, ref))
           if not np.array_equal(g, r)]
    if len(got) != len(ref) or bad:
        k = bad[0] if bad else None
        diff = (float(np.max(np.abs(got[k] - ref[k])))
                if k is not None else None)
        raise AssertionError(
            f"{name}: {len(bad)} of {len(ref)} requests differ from the "
            f"reference (first: request {k}, max |diff| {diff}); "
            f"{len(got)} answered")


def _config(arch: str, data):
    """The paper's GNN ``arch`` (``configs/qgtc_gnn.py``) sized to ``data``."""
    import dataclasses

    from repro.configs.qgtc_gnn import GNN_CONFIGS

    return dataclasses.replace(GNN_CONFIGS[arch],
                               in_dim=data.features.shape[1],
                               n_classes=data.n_classes, x_bits=FEAT_BITS,
                               w_bits=FEAT_BITS)


def _setup(seed: int):
    import jax
    from repro.graph import datasets, partition
    from repro.models import gnn
    from repro.serve import requests_from_partitions

    t0 = time.perf_counter()
    data = datasets.load(DATASET, scale=1.0, seed=seed)
    parts = partition.partition(data.csr, PARTS)
    reqs = requests_from_partitions(data, parts)
    cfg = _config("qgtc-gcn", data)
    qparams = gnn.quantize_params(
        gnn.init_params(jax.random.PRNGKey(seed), cfg), cfg)
    _log(f"data {DATASET}: {data.csr.n} nodes, "
         f"{data.csr.e} edges, {data.features.shape[1]} "
         f"features, {len(reqs)} requests; host set-up "
         f"{time.perf_counter() - t0:.3f} s")
    return data, parts, reqs, cfg, qparams


def serve_phase(reqs, cfg, qparams) -> None:
    from repro import api
    from repro.serve import GNNServer
    from repro.serve.queue import MicroBatcher, buckets_for

    buckets = buckets_for(reqs, levels=3)
    _log(f"buckets n_pad {[b.n_pad for b in buckets]}")

    def server(backend, policy=None):
        # room for every subgraph and every composed batch: the second
        # round is all cache hits
        return GNNServer(qparams, cfg, feat_bits=FEAT_BITS, backend=backend,
                         policy=policy, buckets=buckets,
                         cache_entries=4 * len(reqs))

    ref = _serve_round(server("xla_dot"), reqs)
    probe = MicroBatcher(buckets)
    probe.add(_fresh(reqs[0]))
    batch = probe.next_plan().batch
    for jump in JUMPS:
        srv = server("pallas", api.ExecutionPolicy(jump=jump))
        if "tpu_custom_call" not in srv.lowered(batch).as_text():
            raise AssertionError(f"jump={jump}: the jitted forward holds "
                                 f"no Pallas TPU kernel")
        for label in ("cold", "warm"):
            st = srv.stats
            b0, h0, t0 = st.batches, st.cache_hits, time.perf_counter()
            got = _serve_round(srv, reqs)
            wall = time.perf_counter() - t0
            _check_equal(f"serve jump={jump} {label}", got, ref)
            _log(f"serve pallas jump={jump} {label}: {len(got)} requests "
                 f"bit-identical to xla_dot in {st.batches - b0} batches, "
                 f"batch cache hit rate "
                 f"{(st.cache_hits - h0) / (st.batches - b0):.3f}, "
                 f"n_compiles {srv.n_compiles}, wall {wall:.3f} s "
                 f"(smoke numbers, compiles included)")


def train_phase(data, parts, arch: str) -> None:
    from repro.train import trainer

    cfg = _config(arch, data)
    losses = {}
    for backend in ("pallas", "xla_dot"):
        tcfg = trainer.TrainConfig(steps=TRAIN_STEPS, log_every=1,
                                   path="int_bitserial", backend=backend,
                                   grad_bits=8, stochastic=False)
        t0 = time.perf_counter()
        _, _, hist = trainer.train(data, parts, cfg, tcfg)
        losses[backend] = [h["loss"] for h in hist]
        _log(f"train {arch} int_bitserial backend={backend}: losses "
             f"{losses[backend]}, wall {time.perf_counter() - t0:.3f} s "
             f"(smoke number, compiles included)")
    got, ref = losses["pallas"], losses["xla_dot"]
    if len(got) != TRAIN_STEPS or not np.all(np.isfinite(got)):
        raise AssertionError(f"train {arch}: expected {TRAIN_STEPS} finite "
                             f"losses, got {got}")
    if got != ref:
        raise AssertionError(f"train {arch}: pallas losses {got} != xla_dot "
                             f"{ref}")


def replica_phase(reqs, cfg, qparams, n_chips: int) -> None:
    import jax
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_local_mesh
    from repro.serve import GNNServer
    from repro.serve.queue import buckets_for

    buckets = buckets_for(reqs, levels=3)
    mesh = make_local_mesh()
    # every request rides alone (a node budget of one composition tile):
    # a batch calibrates its features together, so only single-request
    # batches give logits that cannot depend on how replicas coalesce
    align = GNNServer(qparams, cfg, feat_bits=FEAT_BITS, backend="pallas",
                      buckets=buckets).align

    def run(**kw):
        srv = GNNServer(qparams, cfg, feat_bits=FEAT_BITS, backend="pallas",
                        buckets=buckets, node_budget=align,
                        cache_entries=4 * len(reqs), **kw)
        t0 = time.perf_counter()
        out = _serve_round(srv, reqs)
        _log(f"replicas {srv.stats.replicas_live}: {srv.stats.requests} "
             f"requests, {srv.stats.batches} batches, batches per device "
             f"{srv.stats.device_batches}, wall "
             f"{time.perf_counter() - t0:.3f} s (smoke number)")
        return srv, out

    _, ref = run()
    # the serving launcher's context: the dist "serve" rules over the mesh
    with mesh, shd.shard_ctx(mesh, shd.make_rules("serve")):
        srv, got = run(mesh=mesh, replicas=n_chips)
    _check_equal(f"{n_chips} replicas", got, ref)
    used = {str(d) for d in jax.devices()[:n_chips]}
    ran = {k for k, v in srv.stats.device_batches.items() if v > 0}
    if ran != used:
        raise AssertionError(f"devices that ran batches {sorted(ran)} != "
                             f"the {n_chips} chips {sorted(used)}")
    _log(f"{n_chips} replicas on {n_chips} chips: {len(got)} requests "
         f"bit-identical to one replica on one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on "
              f"{devs[0].platform!r}); this smoke needs a TPU chip",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devs)}", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log(f"device {json.dumps(device)}")

    from repro.launch.compile_cache import init_compile_cache

    _log(f"compile cache {init_compile_cache()}")
    from repro import api

    # what the launchers get when no engine is chosen
    default_engine = api.current()[0].name
    if default_engine != "pallas":
        raise AssertionError(f"the default engine on a TPU is "
                             f"{default_engine!r}, not the Pallas kernels")
    _log(f"default engine {default_engine}")
    # the Pallas path is pinned: a silent reroute to another backend (or a
    # tuning table falling back) is a failure here, not a warning
    warnings.filterwarnings("error", message=".*falling back.*",
                            category=RuntimeWarning)
    t0 = time.perf_counter()
    data, parts, reqs, cfg, qparams = _setup(args.seed)
    if args.chips == 4:
        replica_phase(reqs, cfg, qparams, args.chips)
    else:
        serve_phase(reqs, cfg, qparams)
        for arch in TRAIN_ARCHS:
            train_phase(data, parts, arch)
    _log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
