"""Serving launcher: LM batched prefill+decode, and GNN continuous batching.

LM mode (``--arch``): continuous-batching-lite — requests accumulate into a
fixed-size batch slot array; each engine step decodes one token for every
live slot; finished slots (EOS or max tokens) are refilled from the queue.
Runs real decoding on local devices with smoke-scale models; the
full-config serving path is exercised by the dry-run (prefill_32k /
decode_32k / long_500k lower serve steps on the production mesh).

Weight-only quantization (``--wq-bits 4``) applies the QGTC bit compression
to every large projection through ``repro.api.nn.quantize_lm_params`` —
the same registry-dispatched pipeline the GNN stack uses — shrinking HBM
decode traffic.

GNN mode (``--gnn DATASET``): streams repeat subgraph traffic through the
``repro.serve.GNNServer`` continuous-batching engine (queue + shape
buckets + tile cache, see docs/serve.md) under the ``repro.dist`` "serve"
rule table, and prints the ServeStats summary (p50/p95 after device sync).
``--arch`` then names the paper's GNN in ``configs/qgtc_gnn.py``
(``qgtc-gcn``, the default, or ``qgtc-gin``).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --requests 12 --max-new 16 --wq-bits 4
  PYTHONPATH=src python -m repro.launch.serve --gnn ogbn-arxiv --scale \
      0.008 --rounds 3 [--arch qgtc-gin]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.api import nn as qnn
from repro.configs.base import smoke_config
from repro.configs.qgtc_gnn import GNN_CONFIGS
from repro.dist import sharding as shd
from repro.launch.compile_cache import init_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.perf import report
from repro.train import data as data_lib


class DecodeEngine:
    """Fixed-batch decode engine with slot refill (continuous batching)."""

    def __init__(self, cfg, params, batch_slots: int, max_seq: int):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.batch = batch_slots
        self._decode = jax.jit(
            lambda p, c, t: lm.decode_step(p, c, t, cfg))
        self._prefill = jax.jit(
            lambda p, b: lm.prefill(p, b, cfg, max_seq=max_seq))

    def generate(self, prompts: np.ndarray, max_new: int,
                 eos_id: int | None = None) -> tuple[np.ndarray, dict]:
        """prompts (B, T0) int32 -> generated (B, max_new). Greedy."""
        b, t0 = prompts.shape
        assert b == self.batch
        batch = {"tokens": jnp.asarray(prompts)}
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (b, self.cfg.n_patches, self.cfg.d_model), jnp.bfloat16)
        if self.cfg.family == "audio_encdec":
            batch["frames"] = jnp.zeros(
                (b, self.cfg.n_frames, self.cfg.d_model), jnp.bfloat16)
        t_start = time.time()
        logits, cache = self._prefill(self.params, batch)
        jax.block_until_ready((logits, cache))  # prefill_s = compute, and
        # the first decode step's latency must not absorb the prefill
        prefill_s = time.time() - t_start
        out = np.zeros((b, max_new), np.int32)
        done = np.zeros(b, bool)
        step_lat = []
        t_dec = time.time()
        for i in range(max_new):
            t_step = time.perf_counter()
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.minimum(nxt, self.cfg.vocab - 1)  # clamp padded vocab
            out[:, i] = np.asarray(nxt)  # host copy = device sync point
            step_lat.append(time.perf_counter() - t_step)
            if eos_id is not None:
                done |= out[:, i] == eos_id
                if done.all():
                    out = out[:, : i + 1]
                    break
            if i + 1 < max_new:  # the last token needs no further decode
                logits, cache = self._decode(self.params, cache, nxt[:, None])
        decode_s = time.time() - t_dec
        stats = {
            "prefill_s": round(prefill_s, 3),
            "decode_s": round(decode_s, 3),
            "tokens_generated": int(out.size),
            "tok_per_s": round(out.size / max(decode_s, 1e-9), 1),
            "decode_p50_s": round(report.percentile(step_lat, 50), 5),
            "decode_p95_s": round(report.percentile(step_lat, 95), 5),
        }
        return out, stats


def serve_gnn(args) -> dict:
    """Stream repeat subgraph traffic through the continuous GNN engine."""
    from repro.graph import datasets, partition
    from repro.models import gnn
    from repro.serve import (AdmissionPolicy, FaultInjector, GNNServer,
                             requests_from_partitions)
    from repro.serve.queue import buckets_for

    data = datasets.load(args.gnn, scale=args.scale, seed=args.seed)
    parts = partition.partition(data.csr, args.parts)
    cfg = dataclasses.replace(GNN_CONFIGS[args.arch or "qgtc-gcn"],
                              in_dim=data.features.shape[1],
                              n_classes=data.n_classes,
                              x_bits=args.feat_bits, w_bits=args.feat_bits)
    params = gnn.init_params(jax.random.PRNGKey(args.seed), cfg)
    qparams = gnn.quantize_params(params, cfg)
    reqs = requests_from_partitions(data, parts)
    buckets = buckets_for(reqs, levels=3)
    admission = None
    if (args.max_queue_depth or args.max_queued_nodes
            or args.max_queued_edges):
        admission = AdmissionPolicy(max_depth=args.max_queue_depth,
                                    max_nodes=args.max_queued_nodes,
                                    max_edges=args.max_queued_edges,
                                    on_full=args.admission)
    # deterministic chaos: --inject-failure specs go through the ONE
    # sanctioned fault source (serve/chaos.py), mirroring
    # launch.train --simulate-failure-at
    chaos = (FaultInjector(*args.inject_failure, seed=args.seed)
             if args.inject_failure else None)
    mesh = make_local_mesh()
    # data-parallel replicas resolve through the dist "serve" rule table;
    # the engine routes INDIVIDUAL subgraphs to replicas by rendezvous
    # fingerprint affinity (repeats hit the replica holding their cached
    # tiles); --replicas decouples the logical fleet from the device count
    with mesh, shd.shard_ctx(mesh, shd.make_rules("serve")):
        server = GNNServer(qparams, cfg, feat_bits=args.feat_bits,
                           buckets=buckets, mesh=mesh, admission=admission,
                           cache_bytes=args.cache_bytes,
                           replicas=args.replicas, chaos=chaos,
                           straggler_tolerance=args.straggler_tolerance)
        for rnd in range(args.rounds):
            for r in reqs:
                server.submit(type(r)(edges=r.edges, features=r.features,
                                      n_nodes=r.n_nodes))
            server.drain()
            st = server.stats
            print(f"[serve-gnn] round {rnd}: compiles={server.n_compiles} "
                  f"cache_hit_rate={server.cache.hit_rate:.2f} "
                  f"shed={st.requests_shed} live={st.replicas_live} "
                  f"retried={st.requests_retried} "
                  f"retry_after={st.retry_after_s:.4f}s", flush=True)
    summary = server.stats.summary()
    summary["model"] = cfg.model
    summary["n_compiles"] = server.n_compiles
    summary["replicas"] = server.stats.replicas_live
    if chaos is not None:
        summary["chaos_fired"] = chaos.fired
        print(f"[serve-gnn] chaos fired: {json.dumps(chaos.fired)}",
              flush=True)
    plan = server.mesh_plan()
    if plan is not None:
        print(f"[serve-gnn] mesh plan for {server.stats.replicas_live} "
              f"live: {plan}", flush=True)
    print(f"[serve-gnn] {json.dumps(summary)}", flush=True)
    return summary


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM architecture to serve; with --gnn, "
                                   "the GNN (qgtc-gcn, the default, or "
                                   "qgtc-gin)")
    ap.add_argument("--gnn", metavar="DATASET",
                    help="serve GNN subgraph traffic from this Table-1 "
                         "dataset instead of an LM")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wq-bits", type=int, default=0,
                    help="weight-only quantize projections to N bits "
                         "(0 = serve full precision)")
    # GNN-mode knobs
    ap.add_argument("--scale", type=float, default=0.008,
                    help="GNN dataset scale factor")
    ap.add_argument("--parts", type=int, default=8,
                    help="GNN partition count (= request granularity)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="GNN traffic rounds (repeats exercise the cache)")
    ap.add_argument("--feat-bits", type=int, default=8)
    ap.add_argument("--cache-bytes", type=int, default=None,
                    help="strict resident-bytes bound on the tile cache "
                         "(LRU; entry count stays the fallback bound)")
    # GNN admission-control knobs (unset = unbounded queue)
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="bound the GNN request queue at N requests")
    ap.add_argument("--max-queued-nodes", type=int, default=None,
                    help="bound the queue at N total queued nodes")
    ap.add_argument("--max-queued-edges", type=int, default=None,
                    help="bound the queue at N total queued edges")
    ap.add_argument("--admission", choices=("reject", "block"),
                    default="reject",
                    help="at the queue bound: shed with a reason (reject) "
                         "or backpressure the producer (block)")
    # GNN elastic-replica knobs
    ap.add_argument("--replicas", type=int, default=None,
                    help="logical replica count for per-subgraph routing "
                         "(default: one per device; more = virtual "
                         "replicas sharing devices round-robin)")
    ap.add_argument("--inject-failure", action="append", default=[],
                    metavar="KIND@BATCH[:k=v,...]",
                    help="deterministic fault injection (repeatable): "
                         "kill@2, stall@1:replica=0,stall_s=0.2, "
                         "slow@3:repeat=4 — mirrors launch.train "
                         "--simulate-failure-at")
    ap.add_argument("--straggler-tolerance", type=float, default=None,
                    help="evict a replica whose batch wall time exceeds "
                         "TOL x its rolling p50 for consecutive batches "
                         "(default: detection off)")
    args = ap.parse_args(argv)
    if args.gnn is None and (args.arch is None or args.arch in GNN_CONFIGS):
        ap.error("pass --arch (LM), or --gnn DATASET with an optional "
                 f"--arch in {sorted(GNN_CONFIGS)} (GNN)")
    if args.gnn is not None and args.arch not in (None, *GNN_CONFIGS):
        ap.error(f"--gnn serves one of {sorted(GNN_CONFIGS)}, not "
                 f"--arch {args.arch}")
    if not 1 <= args.feat_bits <= 8:
        ap.error(f"--feat-bits must be in 1..8, got {args.feat_bits}")
    if args.wq_bits and not 1 <= args.wq_bits <= 8:
        ap.error(f"--wq-bits must be in 1..8 (or 0 to disable), "
                 f"got {args.wq_bits}")
    init_compile_cache()
    if args.gnn:
        return serve_gnn(args)

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = make_local_mesh()
    with mesh, shd.shard_ctx(mesh, shd.make_rules("serve")):
        params, _ = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)
        if args.wq_bits:
            params, qstats = qnn.quantize_lm_params(params, args.wq_bits)
            print(f"[serve] wq{args.wq_bits}: {qstats['n_quantized']} "
                  f"projections, {qstats['bytes_fp16'] / 1e6:.1f} MB bf16 -> "
                  f"{qstats['bytes_packed'] / 1e6:.1f} MB packed "
                  f"({qstats['ratio']:.1f}x less HBM decode traffic)",
                  flush=True)
        engine = DecodeEngine(cfg, params, args.batch_slots,
                              max_seq=args.prompt_len + args.max_new + 8)
        served = 0
        all_stats = []
        while served < args.requests:
            n = min(args.batch_slots, args.requests - served)
            toks, _ = data_lib.synthetic_batch(
                jnp.asarray(args.seed), jnp.asarray(served),
                batch=args.batch_slots, seq=args.prompt_len, vocab=cfg.vocab)
            out, stats = engine.generate(np.asarray(toks), args.max_new)
            stats["live_slots"] = n
            all_stats.append(stats)
            served += n
            print(f"[serve] {json.dumps(stats)}", flush=True)
        total_tok = sum(s["tokens_generated"] for s in all_stats)
        print(f"[serve] served {served} requests, {total_tok} tokens",
              flush=True)
        return {"requests": served, "stats": all_stats}


if __name__ == "__main__":
    main()
