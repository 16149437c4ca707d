"""Training driver: the integer Cluster-GCN step, as ``trainer.train``
drives it.

Batches of ``batch_parts`` parts come from ``trainer.prepare_batches`` in
the order ``batch_iterator`` gives for the seed; the first ``batches`` of
that order are cycled (each step's device work is the same whichever
batch it is). Set-up builds their ``intpath.ArtifactCache`` artifacts,
makes the weights from the seed and runs the first ``checked_steps``
steps through the program's own jitted step
(``trainer._train_step_int``), keeping the losses, the optimizer state
after the first step and the parameters after the last. The window then
drives the same step on the same state: it keeps at most ``in_flight``
steps queued on the device, never reads a loss, and ends when the last
step's outputs are ready.

The check runs the plain reference through the same first steps after
the window and compares each step's loss (``loss_gap``: the widest
relative gap), the first gradient as the optimizer's first moment holds
it (``grad_gap``: the worst leaf's gap of norms) and the parameters'
change after those steps (``update_gap``: the median leaf's gap of
norms; a single leaf's is the noise of the later steps, see PERF.md).
Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both leaf numbers.
"""
from __future__ import annotations

import collections
import itertools
import time

import numpy as np

from bench import weights

__all__ = ["Cell"]


def _leaf_gaps(got: dict, ref: dict, keep: set) -> list:
    """Each kept leaf's gap of norms, against the larger of that leaf's
    reference norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    return [abs(float(np.linalg.norm(got[k])) - norms[k])
            / max(norms[k], med, 1e-30) for k in sorted(keep)]


def _flat(tree: dict) -> dict:
    return {f"{layer}/{name}": np.asarray(v, np.float64)
            for layer, group in tree.items() for name, v in group.items()}


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, ctx,
                 seconds: float):
        self.cfg, self.mix, self.seed, self.ctx = cfg, mix, int(seed), ctx
        self.state = None

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.graph import datasets, partition
        from repro.graph.batching import batch_iterator
        from repro.models import gnn
        from repro.train import intpath, trainer
        from repro.train import optimizer as opt

        cfg, mix, log = self.cfg, self.mix, self.ctx.log
        t0 = time.perf_counter()
        data = datasets.load(cfg["dataset"], scale=cfg.get("scale", 1.0),
                             seed=cfg["graph_seed"])
        parts = partition.partition(data.csr, cfg["cluster_parts"])
        batches = trainer.prepare_batches(data, parts, mix["batch_parts"])
        order = [b for _, b in itertools.islice(
            batch_iterator(batches, epochs=None, seed=self.seed),
            mix["batches"])]
        t1 = time.perf_counter()
        bp, rp = intpath.batch_caps(batches)
        cache = intpath.ArtifactCache(cfg["x_bits"], block_pad=bp,
                                      rem_pad=rp)
        self.feed = [{"art": cache.get(b), "y": jnp.asarray(b.labels),
                      "mask": jnp.asarray(b.train_mask)} for b in order]
        self.host_batches = order
        t2 = time.perf_counter()
        log(f"data {data.csr.n} nodes, {len(batches)} batches of "
            f"{mix['batch_parts']} parts (n_pad {order[0].n_nodes}) in "
            f"{t1 - t0:.3f} s; artifacts for {len(order)} in "
            f"{t2 - t1:.3f} s")
        params = weights.make_gnn_params(cfg, self.seed)
        self.p0 = weights.to_numpy(params)
        o = mix["optimizer"]
        self.ocfg = opt.AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                                    eps=o["eps"],
                                    weight_decay=o["weight_decay"],
                                    grad_clip=o["grad_clip"])
        self.mcfg = gnn.GNNConfig(model=cfg["model"],
                                  in_dim=cfg["feature_dim"],
                                  hidden=cfg["hidden"],
                                  n_classes=cfg["num_classes"],
                                  layers=cfg["layers"], x_bits=cfg["x_bits"],
                                  w_bits=cfg["w_bits"], gin_eps=cfg["gin_eps"])
        self.sr_key = jax.random.PRNGKey(self.seed & 0xFFFFFFFF)
        self.trainer = trainer
        self.state = (params, opt.adamw_init(params))
        self.n_step = 0
        losses = []
        for i in range(mix["checked_steps"]):
            losses.append(self._step())
            if i == 0:
                self.mu1 = _flat(jax.device_get(self.state[1]["mu"]))
        jax.block_until_ready(self.state)
        self.p_checked = _flat(jax.device_get(self.state[0]))
        self.losses = [float(x) for x in losses]
        log(f"first {mix['checked_steps']} steps: losses {self.losses}; "
            f"set-up after data {time.perf_counter() - t2:.3f} s")

    def _step(self):
        import jax.numpy as jnp

        params, ostate = self.state
        dbatch = self.feed[self.n_step % len(self.feed)]
        params, ostate, _, loss, _ = self.trainer._train_step_int(
            params, ostate, None, dbatch, self.sr_key,
            jnp.uint32(self.n_step), self.mcfg, self.ocfg,
            self.mix["grad_bits"], self.mix["stochastic"], 0, None)
        self.state = (params, ostate)
        self.n_step += 1
        return loss

    def window(self, seconds: float) -> dict:
        import jax

        depth = int(self.mix["in_flight"])
        queued: collections.deque = collections.deque()
        first = self.n_step
        with self.ctx.traced():
            t0 = time.perf_counter()
            while True:
                with self.ctx.span("step"):
                    queued.append(self._step())
                if len(queued) > depth:
                    with self.ctx.span("wait"):
                        queued.popleft().block_until_ready()
                if time.perf_counter() - t0 >= seconds:
                    break
            with self.ctx.span("wait"):
                jax.block_until_ready(self.state)
            t1 = time.perf_counter()
        steps = self.n_step - first
        rec = {"kind": "train", "window_s": t1 - t0, "steps": steps,
               "attempted": steps, "failed": 0,
               "step_batches": [self._shape(i) for i in
                                range(first, self.n_step)]}
        self.ctx.log(f"window {rec['window_s']:.3f} s: {steps} steps, "
                     f"{rec['window_s'] / steps * 1e3:.3f} ms per step")
        return rec

    def _shape(self, i: int) -> dict:
        b = self.host_batches[i % len(self.host_batches)]
        e = np.asarray(b.edges)
        live = e[0] >= 0
        offs = np.concatenate([[0], np.cumsum(b.part_sizes)])
        blk_s = np.searchsorted(offs, e[0][live], side="right")
        blk_d = np.searchsorted(offs, e[1][live], side="right")
        return {"sizes": [int(s) for s in b.part_sizes],
                "cross_edges": int(np.sum(blk_s != blk_d))}

    def release(self) -> None:
        self.state = None
        self.feed = None

    # ----------------------------------------------------------------- check

    def reference(self, bits: dict, mask_half: bool = False) -> dict:
        from bench import registry

        ref = registry.reference(self.cfg["reference"], self.ctx.root)
        batches = []
        for b in self.host_batches[:self.mix["checked_steps"]]:
            n = b.n_nodes
            e = np.asarray(b.edges)
            live = e[0] >= 0
            adj = np.zeros((n, n), np.float64)
            adj[e[0][live], e[1][live]] = 1.0
            mask = np.asarray(b.train_mask).copy()
            if mask_half:
                mask[np.flatnonzero(mask)[1::2]] = False
            batches.append({"adj": adj, "x": b.features, "y": b.labels,
                            "mask": mask})
        return ref.adamw_steps(self.p0, self.cfg, batches,
                               self.mix["optimizer"], bits)

    def bits(self, b: int | None = None) -> dict:
        return {"x": b or self.cfg["x_bits"], "w": b or self.cfg["w_bits"],
                "g": b or self.mix["grad_bits"]}

    def numbers(self, losses, mu1_grads: dict, p_after: dict,
                ref: dict) -> dict:
        """The three compared numbers of a run against the reference."""
        r_first = _flat(ref["first_grads"])
        r_delta = {k: v - self.p0_flat[k]
                   for k, v in _flat(ref["params"]).items()}
        norms = {k: float(np.linalg.norm(v)) for k, v in r_first.items()}
        med = float(np.median(list(norms.values())))
        keep = {k for k, v in norms.items() if v >= 1e-3 * med}
        delta = {k: v - self.p0_flat[k] for k, v in p_after.items()}
        loss_gap = max(abs(a - b) / abs(b) for a, b in
                       zip(losses, ref["losses"]))
        upd = _leaf_gaps(delta, r_delta, keep)
        return {"loss_gap": loss_gap,
                "grad_gap": max(_leaf_gaps(mu1_grads, r_first, keep)),
                "update_gap": float(np.median(upd)),
                "update_gap_worst_leaf": max(upd)}

    @property
    def p0_flat(self) -> dict:
        return _flat(self.p0)

    def _program_numbers(self, ref: dict) -> dict:
        b1 = self.mix["optimizer"]["b1"]
        grads = {k: v / (1.0 - b1) for k, v in self.mu1.items()}
        return self.numbers(self.losses, grads, self.p_checked, ref)

    def control(self, rec: dict) -> dict:
        """The numbers with the reference at int4 in the program's place,
        and with the fault of half of each batch left out (the mean taken
        over the rest), planted in the reference."""
        ref = self.reference(self.bits())
        out = {}
        for tag, got in (("bits4", self.reference(self.bits(4))),
                         ("bits7", self.reference(self.bits(7))),
                         ("half_batch", self.reference(self.bits(),
                                                       mask_half=True))):
            nums = self.numbers(got["losses"], _flat(got["first_grads"]),
                                _flat(got["params"]), ref)
            out.update({f"{k}_{tag}": v for k, v in nums.items()})
        return out

    def check(self, rec: dict) -> list:
        nums = self._program_numbers(self.reference(self.bits()))
        return [(k, nums[k], lim) for k, lim in self.ctx.limits.items()]
