"""Jaxpr-level abstract-trace checker for the QGTC execution contracts.

The lint rules (repro.analysis.rules) catch contract violations the AST
can see; this module proves the ones only the traced computation can:

  * **Integer purity** — ``jax.make_jaxpr`` traces every registered
    backend's ``bgemm`` / ``bitserial_mm`` / jump / sgt path under
    abstract int inputs across 1-8 bits and asserts NO floating-point
    primitive appears anywhere in the jaxpr (recursively through pjit /
    pallas_call / cond sub-jaxprs).  The fused §4.5 path is float by
    design in its epilogue, so there the assertion weakens to: no float
    ``dot_general`` (the GEMM itself stays integer), float ops restricted
    to an elementwise-epilogue allowlist, and an integer output dtype.
  * **``tiles=`` contract** — compact 3-tuples ``(idx, counts, s_max)``
    and tagged sgt 4-tuples ``(idx, counts, s_w, "sgt")`` must trace
    cleanly on capable backends; a device-array ``s_max`` must raise
    TypeError (it would size the kernel grid from a traced value); an
    unknown tag must raise ValueError; backends WITHOUT the jump
    capability must have ``tiles=`` stripped by dispatch and still trace
    pure.
  * **ExecutionPolicy grid validity** — every construction site the
    linter collects (repro.analysis.rules.policy_sites) is re-validated,
    reported with file:line; dynamic sites are counted so coverage is
    visible.

Tracing is abstract: nothing executes on device, so the full sweep
(3 backends x 1-8 bits x ops x jump arms) runs in seconds and is cheap
enough for the CI lint job (``python -m repro.analysis.trace``).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["run_trace_checks", "check_backend", "check_policy_sites",
           "check_train_path", "iter_jaxprs", "float_eqns", "main"]

# container/structural primitives may carry float avals through to a
# sub-jaxpr or shuffle epilogue values without doing float MATH; the fused
# path allows exactly these plus elementwise epilogue arithmetic
_EPILOGUE_OK = {
    # containers (contents are checked recursively)
    "jit", "pjit", "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "remat", "checkpoint", "cond", "while", "scan", "pallas_call",
    # data movement (incl. pallas Ref reads/writes of the alpha/beta refs)
    "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
    "pad", "slice", "dynamic_slice", "dynamic_update_slice", "squeeze",
    "expand_dims", "concatenate", "select_n", "gather", "scatter",
    "copy", "stop_gradient", "get", "swap", "addupdate", "load", "store",
    "masked_load", "masked_store",
    # elementwise rescale/requantize epilogue math (§4.5)
    "mul", "add", "sub", "div", "max", "min", "floor", "ceil", "clamp",
    "sign", "abs", "neg", "ge", "gt", "le", "lt", "eq", "ne",
}

# the GEMM primitives that must never run in float on any path
_GEMM_PRIMS = {"dot_general", "conv_general_dilated"}


# ------------------------------------------------------------- jaxpr walking

def _sub_jaxprs(value):
    """Extract Jaxpr objects from an eqn param value (ClosedJaxpr, Jaxpr,
    or nested lists/tuples of them — covers pjit, cond branches, scan,
    and pallas_call's ``jaxpr`` param)."""
    if hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr
    elif hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params."""
    closed = getattr(jaxpr, "jaxpr", None)
    if closed is not None and hasattr(closed, "eqns"):
        jaxpr = closed
    seen, stack = set(), [jaxpr]
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        yield jx
        for eqn in jx.eqns:
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))


def _is_float(var) -> bool:
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.floating)


def float_eqns(jaxpr):
    """Yield ``(primitive_name, eqn)`` for every eqn touching a float aval
    anywhere in the (recursive) jaxpr."""
    for jx in iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if any(_is_float(v) for v in list(eqn.invars) + list(eqn.outvars)):
                yield eqn.primitive.name, eqn


def _purity_failures(jaxpr, label, *, fused: bool) -> list:
    fails = []
    for name, eqn in float_eqns(jaxpr):
        if not fused:
            fails.append(f"{label}: float primitive {name!r} in a "
                         f"non-fused integer path")
        elif name in _GEMM_PRIMS:
            fails.append(f"{label}: {name!r} runs in float — the GEMM "
                         f"itself must stay integer even on the fused path")
        elif name not in _EPILOGUE_OK:
            fails.append(f"{label}: float primitive {name!r} outside the "
                         f"elementwise §4.5 epilogue allowlist")
    out_avals = getattr(jaxpr, "out_avals", None) or jaxpr.jaxpr.outvars
    for aval in out_avals:
        dtype = getattr(aval, "dtype", None)
        if dtype is not None and jnp.issubdtype(dtype, jnp.floating):
            fails.append(f"{label}: float output dtype {dtype} — every "
                         f"bitserial/bgemm path returns integers")
    return sorted(set(fails))


# ------------------------------------------------------------ trace harness

def _operands(m, k, n, s, t):
    from repro.core import bitops
    rng = np.random.default_rng(s * 8 + t)
    a = rng.integers(0, 1 << s, (m, k)).astype(np.int32)
    b = rng.integers(0, 1 << t, (k, n)).astype(np.int32)
    return (bitops.pack_a(jnp.asarray(a), s),
            bitops.pack_b(jnp.asarray(b), t))


def check_backend(be, *, bits=range(1, 9), shape=(16, 256, 128),
                  log=lambda *_: None) -> tuple:
    """Trace one backend's ops across bit widths; returns
    ``(checks_run, failures)``."""
    from repro import api
    from repro.api.policy import DEFAULT_POLICY
    from repro.core import zerotile
    from repro.kernels import sgt as sgt_lib

    be = api.get_backend(be)
    pol = DEFAULT_POLICY  # explicit policy: dispatch never consults a table
    m, k, n = shape
    checks, fails = 0, []

    def trace(label, fn, *args, fused=False):
        nonlocal checks
        checks += 1
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as e:  # tracing itself must not explode
            fails.append(f"{label}: trace failed: {type(e).__name__}: {e}")
            return
        fails.extend(_purity_failures(jaxpr, label, fused=fused))

    def expect(label, exc, fn, *args):
        nonlocal checks
        checks += 1
        try:
            jax.make_jaxpr(fn)(*args)
        except exc:
            return
        except Exception as e:
            fails.append(f"{label}: expected {exc.__name__}, got "
                         f"{type(e).__name__}: {e}")
            return
        fails.append(f"{label}: expected {exc.__name__}, traced cleanly")

    # --- bgemm: the 1-bit kernel --------------------------------------
    ap1, bp1 = _operands(m, k, n, 1, 1)
    if be.supports("bgemm"):
        trace(f"{be.name}:bgemm",
              lambda a, b: api.bgemm(a, b, backend=be, policy=pol),
              ap1[0], bp1[0])

    # --- bitserial across 1-8 bits (plus asymmetric corners) ----------
    pairs = [(b, b) for b in bits] + [(1, 8), (8, 1)]
    for s, t in sorted(set(pairs)):
        if not be.supports("bitserial_mm", s=s, t=t):
            continue
        ap, bp = _operands(m, k, n, s, t)
        trace(f"{be.name}:bitserial_mm:{s}x{t}b",
              lambda a, b: api.bitserial_mm_packed(a, b, backend=be,
                                                   policy=pol),
              ap, bp)

    # --- fused requantize epilogue (§4.5): float allowed, gated -------
    alpha = jnp.full((m, 1), 0.01, jnp.float32)
    beta = jnp.zeros((1, n), jnp.float32)
    for s in bits:
        if not be.supports("bitserial_fused", s=s, t=s):
            continue
        ap, bp = _operands(m, k, n, s, s)
        trace(f"{be.name}:bitserial_fused:{s}b",
              lambda a, b, al, bt: api.bitserial_fused(
                  a, b, al, bt, out_bits=4, backend=be, policy=pol),
              ap, bp, alpha, beta, fused=True)

    # --- zero-tile jumping + tiles= contract --------------------------
    ap, bp = _operands(m, k, n, 2, 2)
    compact = zerotile.compact_artifacts(ap, pol.block_m, pol.block_w)
    if be.supports("bitserial_jump"):
        trace(f"{be.name}:bitserial_mm:jump=mask",
              lambda a, b: api.bitserial_mm_packed(
                  a, b, backend=be, policy=pol.replace(jump="mask")),
              ap, bp)
        trace(f"{be.name}:bitserial_mm:tiles=compact",
              lambda a, b: api.bitserial_mm_packed(a, b, backend=be,
                                                   policy=pol,
                                                   tiles=compact),
              ap, bp)
        # s_max sizes the kernel grid: a device scalar there must be
        # rejected, not silently synced per call
        bad = (compact[0], compact[1], jnp.asarray(compact[2], jnp.int32))
        expect(f"{be.name}:tiles:s_max-device-scalar", TypeError,
               lambda a, b: api.bitserial_mm_packed(a, b, backend=be,
                                                    policy=pol, tiles=bad),
               ap, bp)
        bogus = (compact[0], compact[1], compact[2], "bogus")
        expect(f"{be.name}:tiles:unknown-tag", ValueError,
               lambda a, b: api.bitserial_mm_packed(a, b, backend=be,
                                                    policy=pol, tiles=bogus),
               ap, bp)
    else:
        # dispatch must STRIP tiles for incapable backends — the call
        # traces cleanly and stays integer-pure
        trace(f"{be.name}:bitserial_mm:tiles-stripped",
              lambda a, b: api.bitserial_mm_packed(a, b, backend=be,
                                                   policy=pol,
                                                   tiles=compact),
              ap, bp)
    if be.supports("bitserial_sgt"):
        sgt_tiles = sgt_lib.sgt_artifacts(ap, pol.block_m)
        trace(f"{be.name}:bitserial_mm:tiles=sgt",
              lambda a, b: api.bitserial_mm_packed(a, b, backend=be,
                                                   policy=pol,
                                                   tiles=sgt_tiles),
              ap, bp)
    log(f"[trace] {be.name}: {checks} checks, {len(fails)} failures")
    return checks, fails


def check_policy_sites(paths=None, rel_root=None) -> tuple:
    """Re-validate every ExecutionPolicy construction site the linter can
    see; returns ``(sites, dynamic, failures)`` with file:line context."""
    from repro.analysis.rules import policy_sites
    from repro.api.policy import DEFAULT_POLICY, ExecutionPolicy

    sites = policy_sites.collect_sites(paths, rel_root)
    dynamic, fails = 0, []
    for s in sites:
        if s["kwargs"] is None:
            dynamic += 1  # config-driven; tune/sweep tags rejections
            continue
        try:
            if s["kind"] == "construct":
                ExecutionPolicy(**s["kwargs"])
            else:
                DEFAULT_POLICY.replace(**s["kwargs"])
        except (TypeError, ValueError) as e:
            fails.append(f"{s['path']}:{s['line']}: invalid "
                         f"ExecutionPolicy: {e}")
    return len(sites), dynamic, fails


def check_train_path(*, bits=(4, 8), log=lambda *_: None) -> tuple:
    """Prove the int_bitserial TRAINING forward contains no float GEMM.

    Abstract-traces ``models.gnn.forward_int`` over synthetic
    IntBatchArtifacts for every registered backend and asserts no
    ``dot_general``/``conv_general_dilated`` operates on float avals:
    every matmul in the training forward — feature/weight GEMMs and both
    halves of the blocked aggregation — must run on integers. Float is
    expected (and allowed) in the affine-correction/requantize epilogues
    and the loss; the claim the int path makes is about the GEMMs.
    """
    from repro import api
    from repro.core.quantize import QuantParams
    from repro.models import gnn
    from repro.train.intpath import IntBatchArtifacts

    bcount, p, d = 2, 32, 32
    n = bcount * p
    rng = np.random.default_rng(0)
    adj_blocks = rng.integers(0, 2, (bcount, p, p)).astype(np.int32)
    rem = -np.ones(16, np.int32)
    rem[:4] = [0, 1, p, p + 1]
    deg = adj_blocks.sum(axis=2).reshape(n, 1).astype(np.float32)
    checks, fails = 0, []
    for nbits in bits:
        art = IntBatchArtifacts(
            adjb=jnp.asarray(adj_blocks),
            row_idx=jnp.arange(n, dtype=jnp.int32).reshape(bcount, p),
            rem_src=jnp.asarray(rem), rem_dst=jnp.asarray(rem),
            deg=jnp.asarray(deg), deg_in=jnp.asarray(deg),
            inv_deg=jnp.asarray(1.0 / (deg + 1.0)),
            xq=jnp.asarray(rng.integers(0, 1 << nbits, (n, d)), jnp.int32),
            qpx=QuantParams(nbits=nbits, scale=jnp.float32(0.1),
                            zero=jnp.float32(0.0)),
            tiles=None, s_maxes=None)
        cfg = gnn.GNNConfig.paper_gcn(d, 10, x_bits=nbits, w_bits=nbits)
        params = gnn.init_params(jax.random.PRNGKey(0), cfg)
        for name in api.list_backends():
            targets = {
                f"train:{name}:forward_int:{nbits}b":
                    lambda pr, n=name: gnn.forward_int(pr, art, cfg,
                                                       backend=n),
                # with grad_bits > 0 the BACKWARD GEMMs are bitserial too,
                # so the whole VJP must trace without a float GEMM
                f"train:{name}:grad:{nbits}b":
                    lambda pr, n=name: jax.grad(lambda p: jnp.sum(
                        gnn.forward_int(p, art, cfg, backend=n,
                                        grad_bits=nbits)))(pr),
            }
            for label, fn in targets.items():
                checks += 1
                try:
                    jaxpr = jax.make_jaxpr(fn)(params)
                except Exception as e:
                    fails.append(f"{label}: trace failed: "
                                 f"{type(e).__name__}: {e}")
                    continue
                for prim, _ in float_eqns(jaxpr):
                    if prim in _GEMM_PRIMS:
                        fails.append(
                            f"{label}: {prim!r} runs in float — the int "
                            f"training path must keep every GEMM integer")
    fails = sorted(set(fails))
    log(f"[trace] train path: {checks} checks, {len(fails)} failures")
    return checks, fails


def run_trace_checks(backends=None, *, bits=range(1, 9), shape=(16, 256, 128),
                     log=print) -> dict:
    """Full sweep: every (probed) backend x op x bit width, plus the
    linter-collected policy sites.  Returns a JSON-able report."""
    from repro import api

    if backends is None:
        backends = api.list_backends()
    report = {"backends": [], "checks": 0, "failures": []}
    for be in backends:
        name = getattr(be, "name", be)
        checks, fails = check_backend(be, bits=bits, shape=shape, log=log)
        report["backends"].append(str(name))
        report["checks"] += checks
        report["failures"].extend(fails)
    n_sites, dynamic, site_fails = check_policy_sites()
    report["policy_sites"] = {"total": n_sites, "dynamic": dynamic,
                              "validated": n_sites - dynamic}
    report["checks"] += n_sites - dynamic
    report["failures"].extend(site_fails)
    log(f"[trace] policy sites: {n_sites - dynamic} validated, "
        f"{dynamic} dynamic")
    t_checks, t_fails = check_train_path(log=log)
    report["train_path"] = {"checks": t_checks, "failures": len(t_fails)}
    report["checks"] += t_checks
    report["failures"].extend(t_fails)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="abstract-trace checker: integer purity, tiles= "
                    "contract, policy-site grid validity")
    ap.add_argument("--backends", nargs="*", default=None,
                    help="backend names (default: all registered)")
    ap.add_argument("--max-bits", type=int, default=8,
                    help="check 1..N bit operands (default 8)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)
    log = (lambda *_: None) if args.json else print
    report = run_trace_checks(args.backends, bits=range(1, args.max_bits + 1),
                              log=log)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        for f in report["failures"]:
            print(f"[trace] FAIL {f}")
        print(f"[trace] {report['checks']} checks over "
              f"{', '.join(report['backends'])}: "
              f"{len(report['failures'])} failures")
    return 1 if report["failures"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
