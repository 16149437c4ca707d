"""LM training launcher: resume-from-latest state machine with fault
injection, straggler watchdog, and atomic checkpointing.

Runs REAL training on whatever devices exist (CPU in this container — use
reduced/smoke configs or --d-model overrides; the full configs are
exercised by dryrun.py). The loop structure is the 1000-node posture:

  1. restore latest checkpoint if present (elastic: any mesh)
  2. deterministic data stream addressed by (seed, step)  -> no data state
  3. jit'd train_step with donated params/opt
  4. atomic checkpoint every --ckpt-every steps
  5. --simulate-failure-at N: hard-exit mid-run; rerunning the same command
     resumes from the last checkpoint and reproduces the remaining steps
  6. straggler watchdog logs p50/p95 and flags slow steps

Example (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

GNN archs (qgtc-gcn / qgtc-gin) take the same resume/failure-injection
loop over Cluster-GCN subgraph batches; ``--int-path`` trains through the
integer bitserial forward (repro.train path="int_bitserial"):
  PYTHONPATH=src python -m repro.launch.train --arch qgtc-gcn --smoke \
      --steps 30 --int-path --ckpt-dir /tmp/gnn-ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import smoke_config
from repro.dist import checkpoint as ckpt
from repro.dist import sharding as shd
from repro.dist.elastic import StragglerWatchdog
from repro.launch import steps as step_lib
from repro.launch.compile_cache import init_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.train import data as data_lib
from repro.train import optimizer as opt


def _train_gnn(cfg, args) -> dict:
    """Cluster-GCN training with the LM launcher's resume/failure posture.

    Same loop contract as the LM branch: deterministic (seed, step) ->
    batch stream (resume just skips consumed steps), atomic checkpoints,
    --simulate-failure-at hard exit, straggler watchdog. ``--int-path``
    swaps the QAT fake-quant step for the integer bitserial step over
    per-batch cached artifacts.
    """
    from repro.graph import partition
    from repro.graph.batching import batch_iterator
    from repro.graph.datasets import load as load_dataset
    from repro.models import gnn
    from repro.train import intpath, trainer

    scale = min(args.scale, 0.05) if args.smoke else args.scale
    data = load_dataset(args.dataset, scale=scale, seed=args.seed)
    parts = partition.partition(data.csr, args.parts)
    cfg = dataclasses.replace(cfg, in_dim=data.features.shape[1],
                              n_classes=int(data.labels.max()) + 1)
    tcfg = trainer.TrainConfig(
        steps=args.steps, lr=args.lr, seed=args.seed,
        log_every=args.log_every,
        path="int_bitserial" if args.int_path else "fake",
        grad_bits=args.grad_bits, stochastic=args.stochastic,
        grad_compress_bits=args.grad_compress_bits)
    ocfg = opt.AdamWConfig(lr=tcfg.lr, weight_decay=tcfg.weight_decay,
                           grad_clip=1.0)
    cfg_hash = ckpt.config_hash((cfg, tcfg, ocfg))

    params = gnn.init_params(jax.random.PRNGKey(args.seed), cfg)
    ostate = opt.adamw_init(params)
    # EF residuals are NOT checkpointed (like the LM branch): after a
    # restart compression re-warms from zero residual, which only re-biases
    # the first post-resume step by one quantization error.
    cstate = (opt.compression_init(params) if tcfg.grad_compress_bits
              else None)
    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        (params, ostate), manifest = ckpt.restore(
            args.ckpt_dir, (params, ostate), cfg_hash=cfg_hash)
        start_step = manifest["step"]
        print(f"[train] resumed from step {start_step}", flush=True)

    batches = trainer.prepare_batches(data, parts, batch_size=4)
    use_int = tcfg.path == "int_bitserial"
    if use_int:
        bp, rp = intpath.batch_caps(batches)
        cache = intpath.ArtifactCache(cfg.x_bits, block_pad=bp, rem_pad=rp)
        dev_batches: dict[int, dict] = {}
    sr_key = jax.random.PRNGKey(args.seed + 0x5eed)
    watchdog = StragglerWatchdog()
    history = []
    for step, batch in batch_iterator(batches, epochs=None, seed=args.seed):
        if step >= args.steps:
            break
        if step < start_step:
            continue  # deterministic stream: resume = skip consumed steps
        t0 = time.time()
        if use_int:
            dbatch = dev_batches.get(id(batch))
            if dbatch is None:
                dbatch = {"art": cache.get(batch),
                          "y": jnp.asarray(batch.labels),
                          "mask": jnp.asarray(batch.train_mask)}
                dev_batches[id(batch)] = dbatch
            params, ostate, cstate, loss, acc = trainer._train_step_int(
                params, ostate, cstate, dbatch, sr_key, jnp.uint32(step),
                cfg, ocfg, tcfg.grad_bits, tcfg.stochastic,
                tcfg.grad_compress_bits, tcfg.backend)
        else:
            dbatch = trainer.make_device_batch(batch)
            params, ostate, loss, acc = trainer._train_step(
                params, ostate, dbatch, cfg, ocfg, tcfg.qat)
        loss = float(loss)
        wall = time.time() - t0
        straggle = watchdog.observe(step, wall)
        if step % args.log_every == 0 or step == args.steps - 1:
            rec = {"step": step, "loss": round(loss, 4),
                   "acc": round(float(acc), 4), "wall_s": round(wall, 3),
                   "straggler": straggle}
            history.append(rec)
            print(f"[train] {json.dumps(rec)}", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, (params, ostate),
                      cfg_hash=cfg_hash)
        if args.simulate_failure_at == step:
            print(f"[train] SIMULATED FAILURE at step {step}", flush=True)
            sys.exit(17)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, (params, ostate),
                  cfg_hash=cfg_hash)
    test_acc = trainer.evaluate(params, data, parts, cfg, qat=True)
    print(f"[train] done: test_acc={test_acc:.4f} p50={watchdog.p50:.3f}s "
          f"p95={watchdog.p95:.3f}s flagged={len(watchdog.flagged)}",
          flush=True)
    return {"history": history, "test_acc": test_acc,
            "final_loss": history[-1]["loss"] if history else None}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--simulate-failure-at", type=int, default=-1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--q-chunk", type=int, default=1024)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--grad-compress-bits", type=int, default=0,
                    help="int8/int4 error-feedback gradient compression for "
                         "the DP reduction (0 = off)")
    # GNN-arch (qgtc-*) options
    ap.add_argument("--dataset", default="proteins")
    ap.add_argument("--scale", type=float, default=0.1,
                    help="GNN dataset subsample fraction")
    ap.add_argument("--parts", type=int, default=8,
                    help="Cluster-GCN partition count")
    ap.add_argument("--int-path", action="store_true",
                    help="GNN: train through the integer bitserial forward")
    ap.add_argument("--grad-bits", type=int, default=0,
                    help="GNN int path: quantize backward GEMMs (0 = float)")
    ap.add_argument("--stochastic", action="store_true",
                    help="GNN int path: stochastic rounding")
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = configs.get(args.arch)
    from repro.models import gnn
    if isinstance(cfg, gnn.GNNConfig):
        return _train_gnn(cfg, args)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = make_local_mesh(model=args.model_parallel)
    rules = shd.make_rules("train")
    ocfg = opt.AdamWConfig(lr=args.lr, grad_clip=1.0)
    cfg_hash = ckpt.config_hash((cfg, ocfg))

    with mesh, shd.shard_ctx(mesh, rules):
        params, axes = lm.init_lm(jax.random.PRNGKey(args.seed), cfg)
        p_sh = step_lib.param_shardings(mesh, rules, axes, params)
        params = jax.device_put(params, p_sh)
        ostate = opt.adamw_init(params)
        start_step = 0
        if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            (params, ostate), manifest = ckpt.restore(
                args.ckpt_dir, (params, ostate),
                shardings=(p_sh, step_lib.opt_shardings(mesh, rules, axes, params)),
                cfg_hash=cfg_hash)
            start_step = manifest["step"]
            print(f"[train] resumed from step {start_step}", flush=True)

        from repro.train.optimizer import (compress_grads, compression_init,
                                           decompress_grads)

        if args.grad_compress_bits:
            # compressed-DP variant: grads are quantized with error feedback
            # before the update (the cross-pod payload on a real cluster);
            # the residual state rides alongside the optimizer state.
            def step_raw(params, ostate, cstate, batch):
                (loss, aux), grads = jax.value_and_grad(
                    lm.lm_loss, has_aux=True)(params, batch, cfg,
                                              q_chunk=args.q_chunk)
                q, scales, cstate = compress_grads(
                    grads, cstate, nbits=args.grad_compress_bits)
                grads = decompress_grads(q, scales)
                params, ostate = opt.adamw_update(params, grads, ostate, ocfg)
                return params, ostate, cstate, {"loss": loss}

            cstate = compression_init(params)
            _step = jax.jit(step_raw, donate_argnums=(0, 1, 2))

            def step_fn(params, ostate, batch, _c=[cstate]):
                params, ostate, _c[0], m = _step(params, ostate, _c[0], batch)
                return params, ostate, m
        else:
            step_fn = jax.jit(
                step_lib.make_train_step(cfg, ocfg, q_chunk=args.q_chunk,
                                         n_micro=args.n_micro),
                donate_argnums=(0, 1))
        watchdog = StragglerWatchdog()
        history = []
        for step in range(start_step, args.steps):
            t0 = time.time()
            batch = data_lib.batch_for_arch(cfg, args.seed, step,
                                            args.batch, args.seq)
            params, ostate, metrics = step_fn(params, ostate, batch)
            loss = float(metrics["loss"])
            wall = time.time() - t0
            straggle = watchdog.observe(step, wall)
            if step % args.log_every == 0 or step == args.steps - 1:
                rec = {"step": step, "loss": round(loss, 4),
                       "wall_s": round(wall, 3), "straggler": straggle}
                history.append(rec)
                print(f"[train] {json.dumps(rec)}", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, step + 1, (params, ostate),
                          mesh_shape=mesh.shape, cfg_hash=cfg_hash)
            if args.simulate_failure_at == step:
                print(f"[train] SIMULATED FAILURE at step {step}", flush=True)
                sys.exit(17)
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, args.steps, (params, ostate),
                      mesh_shape=mesh.shape, cfg_hash=cfg_hash)
        print(f"[train] done: p50={watchdog.p50:.3f}s p95={watchdog.p95:.3f}s "
              f"flagged={len(watchdog.flagged)}", flush=True)
        return {"history": history, "final_loss": history[-1]["loss"]
                if history else None}


if __name__ == "__main__":
    main()
