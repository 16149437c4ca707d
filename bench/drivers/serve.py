"""Serving driver: a closed loop of the mix's ``clients`` against the GNN
server.

The server is built as ``python -m repro.launch.serve --gnn`` builds it:
``GNNServer`` with the default engine and policy and a bucket ladder from
``buckets_for`` over a sample of the cell's traffic. Requests come from
the one generator (``bench/generator.py``) in a fixed order per seed. Each
client sends its next request when its previous one is answered, so the
queue never runs dry however fast the server gets, and the window's
throughput reads the server's capacity with no rate to tune. A request is
the node-induced subgraph of the union of its parts, with the graph's
features.

Set-up loads the graph (the same in every run: the configuration's
``graph_seed`` stands for the fixed public dataset), cuts it into parts,
makes the weights from the seed, builds the server, optionally sends
every part of the population once (so the tile cache holds it), and
serves batches of every size the window can form (so every program the
window runs is built).

One thread plays every client and runs the server. A request's latency
runs from its submit to the return of the ``step`` that carries its
logits; the window's throughput counts the nodes answered by the close.
After the close the clients send nothing more, and the few requests still
queued are served, untimed for throughput, so every request sent is
answered. A seeded reservoir sample of the run's batches is then
recomputed by the plain reference, batch by batch as the engine grouped
them (the engine calibrates each batch's features as a whole).
"""
from __future__ import annotations

import time

import numpy as np

from bench import generator, weights

__all__ = ["Cell", "ALIGN", "TILE"]

# The reference lays a batch out as the engine documents it: members at
# 128-node offsets, zero feature rows to the bucket. Only whether a batch
# has padding rows changes its numbers (padding rows are alike and enter
# only the batch-wide calibration), so the exact offsets need not match.
ALIGN = 128
CONTROL_BITS = 4  # int4: the step below the configuration's int8
TILE = 128


def _ladder(top_nodes: int, levels: int) -> list:
    """Bucket node sizes of a ladder topped at ``top_nodes``."""
    out, n = [], top_nodes
    for _ in range(levels):
        out.append(n)
        if n <= TILE:
            break
        n = -(-(n // 2) // TILE) * TILE
    return sorted(set(out))


def _ceil(n: int, m: int) -> int:
    return -(-max(n, 1) // m) * m


def induced_edges(indptr: np.ndarray, indices: np.ndarray,
                  remap: np.ndarray, nodes: np.ndarray) -> np.ndarray:
    """(2, E) int32 edges of the subgraph induced by ``nodes``, relabelled
    0..len-1 in their order, each row's neighbours ascending. ``remap`` is
    a scratch array of -1 over the graph's nodes; it is left as found."""
    starts = indptr[nodes].astype(np.int64)
    lens = indptr[nodes + 1].astype(np.int64) - starts
    src = np.repeat(np.arange(len(nodes), dtype=np.int64), lens)
    first = np.cumsum(lens) - lens
    pos = np.arange(int(lens.sum()), dtype=np.int64) + np.repeat(
        starts - first, lens)
    remap[nodes] = np.arange(len(nodes))
    dst = remap[indices[pos]]
    remap[nodes] = -1
    keep = dst >= 0
    src, dst = src[keep], dst[keep]
    order = np.lexsort((dst, src))
    return np.stack([src[order], dst[order]]).astype(np.int32)


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, ctx,
                 seconds: float):
        self.cfg, self.mix, self.seed, self.ctx = cfg, mix, int(seed), ctx
        self.seconds = float(seconds)
        self.server = None

    # ---------------------------------------------------------------- set-up

    def setup(self) -> None:
        from repro.graph import datasets, partition
        from repro.models import gnn
        from repro.serve import GNNServer
        from repro.serve.queue import buckets_for

        cfg, mix, log = self.cfg, self.mix, self.ctx.log
        t0 = time.perf_counter()
        data = datasets.load(cfg["dataset"], scale=cfg.get("scale", 1.0),
                             seed=cfg["graph_seed"])
        if "scale" not in cfg and data.csr.n != cfg["num_nodes"]:
            raise ValueError(f"{cfg['dataset']} has {data.csr.n} nodes, the "
                             f"configuration {cfg['num_nodes']}")
        if data.features.shape[1] != cfg["feature_dim"]:
            raise ValueError("feature width differs from the configuration")
        parts = partition.partition(data.csr, cfg["cluster_parts"])
        order = np.argsort(parts, kind="stable")
        bounds = np.searchsorted(parts[order],
                                 np.arange(cfg["cluster_parts"] + 1))
        self.part_nodes = [order[bounds[p]:bounds[p + 1]]
                           for p in range(cfg["cluster_parts"])]
        self.data = data
        self._remap = np.full(data.csr.n, -1, np.int64)
        t1 = time.perf_counter()
        self.stream = generator.PartStream(mix, cfg["cluster_parts"],
                                           self.seed)
        self.params = weights.make_gnn_params(cfg, self.seed)
        self.ref_params = weights.to_numpy(self.params)
        mcfg = gnn.GNNConfig(model=cfg["model"], in_dim=cfg["feature_dim"],
                             hidden=cfg["hidden"],
                             n_classes=cfg["num_classes"],
                             layers=cfg["layers"], x_bits=cfg["x_bits"],
                             w_bits=cfg["w_bits"], gin_eps=cfg["gin_eps"])
        qparams = gnn.quantize_params(self.params, mcfg)
        self._built: dict = {}
        if mix["population"] == "hot":
            sample = [self._request((int(p),)) for p in self.stream.members]
        else:
            sample = [self._request(next(self.stream))
                      for _ in range(mix["bucket_sample"])]
        buckets = buckets_for(sample, levels=mix["bucket_levels"])
        self.ladder = _ladder(buckets[-1].n_pad, mix["bucket_levels"])
        self.server = GNNServer(qparams, mcfg, feat_bits=cfg["x_bits"],
                                buckets=buckets,
                                cache_entries=mix["cache_entries"])
        from repro import api
        log(f"data {data.csr.n} nodes {data.csr.e} edges, "
            f"{cfg['cluster_parts']} parts in {t1 - t0:.3f} s; engine "
            f"{api.current()[0].name}; buckets "
            f"{[(b.n_pad, b.e_cap) for b in buckets]}, align "
            f"{self.server.align}")
        t2 = time.perf_counter()
        if mix.get("warm_population"):
            for p in self.stream.members:
                self.server.submit(self._request((int(p),)))
            self.server.drain()
        t3 = time.perf_counter()
        # every batch size the window can form: 1..max members, twice
        per_batch = self.server.batcher.node_budget // _ceil(
            max(r.n_nodes for r in sample), self.server.align)
        for m in list(range(1, per_batch + 1)) * 2:
            for _ in range(m):
                self.server.submit(self._request(next(self.stream)))
            self.server.drain()
        log(f"warm: population {t3 - t2:.3f} s, batches of 1..{per_batch} "
            f"{time.perf_counter() - t3:.3f} s")

    def _request(self, parts: tuple):
        """A fresh request (new id) for the union of ``parts``."""
        from repro.serve.queue import SubgraphRequest

        got = self._built.get(parts)
        if got is None:
            nodes = np.concatenate([self.part_nodes[p] for p in parts])
            got = (self._edges(nodes),
                   np.ascontiguousarray(self.data.features[nodes],
                                        np.float32), len(nodes))
            if not self.stream.unique:
                self._built[parts] = got
        edges, feats, n = got
        return SubgraphRequest(edges=edges, features=feats, n_nodes=n)

    def _edges(self, nodes: np.ndarray) -> np.ndarray:
        csr = self.data.csr
        return induced_edges(csr.indptr, csr.indices, self._remap, nodes)

    # ---------------------------------------------------------------- window

    def _loop(self, seconds: float) -> dict:
        """Closed loop: each of the mix's ``clients`` sends its next request
        when its previous one is answered; a step runs while any is queued.
        Clients send nothing after the close; what they sent before it is
        still served, so every request sent has its answer."""
        srv, ctx = self.server, self.ctx
        sent: list = []          # (part tuple, nodes) in send order
        pending: dict = {}       # req_id -> (send index, send time)
        lat, waits, step_t, step_sizes = [], [], [], []
        failed = nodes = completed = n_steps = 0
        rng = generator.seeded_rng(self.seed, 3)
        k_keep = int(self.mix["check_batches"])
        kept: list = []

        def send() -> None:
            nonlocal failed
            with ctx.span("generate"):
                tup = next(self.stream)
                req = self._request(tup)
            with ctx.span("submit"):
                rid = srv.submit(req)
            if rid is None:
                failed += 1
            else:
                pending[rid] = (len(sent), time.perf_counter())
            sent.append((tup, req.n_nodes))

        t0 = time.perf_counter()
        close = t0 + seconds
        for _ in range(int(self.mix["clients"])):
            send()
        while pending:
            s0 = time.perf_counter()
            with ctx.span("step"):
                out = srv.step(return_logits=True)
            s1 = time.perf_counter()
            n_steps += 1
            members = []
            for rid, (_, logits) in out.items():
                i, t_sent = pending.pop(rid)
                lat.append(s1 - t_sent)
                waits.append(s0 - t_sent)
                members.append((i, logits))
                if s1 <= close:
                    completed += 1
                    nodes += sent[i][1]
            step_t.append((s0, s1))
            step_sizes.append([len(lg) for _, lg in members])
            # seeded reservoir sample of the run's batches
            entry = [(sent[i][0], lg) for i, lg in members]
            if len(kept) < k_keep:
                kept.append(entry)
            else:
                j = int(rng.integers(0, n_steps))
                if j < k_keep:
                    kept[j] = entry
            if s1 <= close:
                for _ in members:
                    send()
        t_end = time.perf_counter()
        return {"t_start": t0, "t_close": close, "t_end": t_end,
                "window_s": seconds, "latencies_s": lat,
                "queue_waits_s": waits, "nodes": nodes,
                "completed": completed, "step_times": step_t,
                "step_sizes": step_sizes, "attempted": len(sent),
                "failed": failed, "kept": kept, "n_steps": n_steps,
                "after_close": len(lat) - completed}

    def window(self, seconds: float) -> dict:
        st = self.server.stats
        before = {k: getattr(st, k) for k in
                  ("batches", "requests", "nodes", "cache_hits",
                   "cache_misses", "cache_partial_hits", "transfer_bytes")}
        with self.ctx.traced():
            rec = self._loop(seconds)
        rec["counters"] = {k: getattr(st, k) - v for k, v in before.items()}
        rec["kind"] = "serve"
        self.ctx.log(
            f"window {seconds:.3f} s, {self.mix['clients']} clients: "
            f"{rec['attempted']} requests sent, {rec['completed']} "
            f"({rec['nodes']} nodes) answered by the close, "
            f"{rec['after_close']} after it, last at "
            f"{rec['t_end'] - rec['t_start']:.3f} s; {rec['n_steps']} "
            f"steps; counters {rec['counters']}")
        return rec

    def release(self) -> None:
        self.server = None
        self.params = None

    # ----------------------------------------------------------------- check

    def reference_batch(self, tups: list, bits: int | None = None) -> list:
        """Reference logits of each member of one batch, laid out as the
        engine lays a batch out: members at ALIGN-node offsets, zero
        feature rows up to the smallest bucket that holds them."""
        ref = self.ctx_reference()
        members = []
        for tup in tups:
            nodes = np.concatenate([self.part_nodes[p] for p in tup])
            members.append(nodes)
        sizes = [len(m) for m in members]
        offs = np.cumsum([0] + [_ceil(s, ALIGN) for s in sizes])
        n_pad = next((b for b in self.ladder if b >= offs[-1]), offs[-1])
        adj = np.zeros((n_pad, n_pad), np.float64)
        x = np.zeros((n_pad, self.cfg["feature_dim"]), np.float64)
        for nodes, off in zip(members, offs[:-1]):
            e = self._edges(nodes)
            adj[e[0] + off, e[1] + off] = 1.0
            x[off:off + len(nodes)] = self.data.features[nodes]
        out = ref.forward(self.ref_params, self.cfg, adj, x, bits=bits)
        return [out[o:o + s] for o, s in zip(offs[:-1], sizes)]

    def ctx_reference(self):
        from bench import registry

        return registry.reference(self.cfg["reference"], self.ctx.root)

    def gap(self, kept: list, against_bits: int | None = None) -> float:
        """Widest logit gap over the kept batches, as a share of the
        reference batch's largest logit magnitude. With ``against_bits``
        the reference at that many bits stands in for the program."""
        worst = 0.0
        for entry in kept:
            tups = [t for t, _ in entry]
            ref = self.reference_batch(tups)
            if against_bits is None:
                got = [np.asarray(lg, np.float64) for _, lg in entry]
            else:
                got = self.reference_batch(tups, bits=against_bits)
            scale = max(float(np.max(np.abs(r))) for r in ref)
            for g, r in zip(got, ref):
                if g.shape != r.shape:
                    return float("inf")
                worst = max(worst, float(np.max(np.abs(g - r))) / scale)
        return worst

    def control(self, rec: dict) -> dict:
        """The check's numbers with the reference one step below the
        configuration's precision (int4 for int8) in the program's place;
        also at 7 bits, which the limit has to fail as well."""
        return {f"logit_gap_bits{b}": self.gap(rec["kept"], against_bits=b)
                for b in (CONTROL_BITS, 7)}

    def check(self, rec: dict) -> list:
        limit = self.ctx.limits["logit_gap"]
        if not rec["kept"]:
            return [("logit_gap", float("inf"), limit)]
        return [("logit_gap", self.gap(rec["kept"]), limit)]
