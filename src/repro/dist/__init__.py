"""Distributed runtime: sharding rules/context, atomic checkpoints,
elastic replanning, and quantized collectives.

Four small modules with one shared convention — *logical* axis names
(what a tensor dimension means: "batch", "qkv_compute", "experts", ...)
are mapped to *mesh* axis names ("pod", "data", "model") by a rule table
from :func:`repro.dist.sharding.make_rules`.  Models only ever talk in
logical names via :func:`repro.dist.sharding.constrain`, which is a no-op
outside a :func:`repro.dist.sharding.shard_ctx` and a
``with_sharding_constraint`` inside one.

See docs/dist.md for the full rule tables, checkpoint layout, and the
compressed-collective semantics (QGTC §4.5 bandwidth-optimized transfer;
Tango-style quantized gradient all-reduce).
"""
from repro.dist import checkpoint, collectives, elastic, sharding
from repro.dist.sharding import (constrain, current_ctx, make_rules,
                                 named_sharding, shard_ctx)

__all__ = ["checkpoint", "collectives", "elastic", "sharding",
           "constrain", "current_ctx", "make_rules", "named_sharding",
           "shard_ctx"]
