"""Backend registry + active-execution context.

One dispatch seam for every quantized GEMM in the repo:

  register(backend)                     — add an engine (plugins welcome)
  get_backend("pallas")                 — look one up
  with use("pallas", policy=pol): ...   — scoped default (contextvar-based,
                                          async/thread safe)
  set_default("popcount")               — process-wide default
  resolve(op, backend=..., policy=...)  — what dispatch calls: explicit
                                          per-call override > active context
                                          > registered-capability fallback

With nothing configured, the default engine follows the device: ``pallas``
on a TPU (the chip runs the paper's kernels), else the first registered
backend.

Fallback: if the active backend can't run an op (probed via
``Backend.supports``), the first *registered* backend that can is used and a
RuntimeWarning is emitted once per (backend, op) pair. An *explicitly*
requested backend never falls back — it raises, so tests pin engines.
"""
from __future__ import annotations

import contextvars
import warnings

import jax

from repro.api.backend import Backend, UnsupportedOpError
from repro.api.policy import DEFAULT_POLICY, ExecutionPolicy

__all__ = [
    "register", "get_backend", "list_backends", "use", "set_default",
    "current", "resolve",
]

_REGISTRY: dict[str, Backend] = {}
_ORDER: list[str] = []  # registration order = fallback priority

# Process-wide default (mutable via set_default); contextvar holds scoped
# overrides as (backend_name | None, policy | None).
_default: tuple[str | None, ExecutionPolicy] = (None, DEFAULT_POLICY)
_active: contextvars.ContextVar[tuple[str | None, ExecutionPolicy | None] | None] = \
    contextvars.ContextVar("repro_api_active", default=None)
_warned_fallbacks: set = set()


def register(backend: Backend, *, override: bool = False) -> Backend:
    if not backend.name or backend.name == "abstract":
        raise ValueError("backend must define a non-default .name")
    if backend.name in _REGISTRY and not override:
        raise ValueError(f"backend {backend.name!r} already registered "
                         "(pass override=True to replace)")
    if backend.name not in _ORDER:
        _ORDER.append(backend.name)
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str | Backend) -> Backend:
    if isinstance(name, Backend):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def list_backends() -> tuple[str, ...]:
    return tuple(_ORDER)


def set_default(backend: str | Backend | None = None,
                policy: ExecutionPolicy | None = None) -> None:
    """Set the process-wide default backend and/or policy."""
    global _default
    name = get_backend(backend).name if backend is not None else _default[0]
    pol = policy if policy is not None else _default[1]
    _default = (name, pol)


class use:
    """Scoped backend/policy default: ``with repro.api.use("pallas", policy=p):``.

    Either argument may be omitted to inherit the surrounding context.
    Re-entrant and safe across threads/async tasks (contextvars).
    """

    def __init__(self, backend: str | Backend | None = None,
                 policy: ExecutionPolicy | None = None):
        self._name = get_backend(backend).name if backend is not None else None
        self._policy = policy
        self._token = None

    def __enter__(self):
        outer = _active.get()
        name = self._name if self._name is not None else (outer or (None, None))[0]
        pol = self._policy if self._policy is not None else (outer or (None, None))[1]
        self._token = _active.set((name, pol))
        return self

    def __exit__(self, *exc):
        _active.reset(self._token)
        return False


def current() -> tuple[Backend, ExecutionPolicy]:
    """The (backend, policy) pair dispatch would use right now."""
    ctx = _active.get()
    name = (ctx[0] if ctx and ctx[0] is not None else _default[0])
    pol = (ctx[1] if ctx and ctx[1] is not None else _default[1])
    if name is None:  # no default configured yet: follow the device
        if not _ORDER:
            raise RuntimeError("no backends registered")
        name = ("pallas" if "pallas" in _REGISTRY
                and jax.default_backend() == "tpu" else _ORDER[0])
    return _REGISTRY[name], pol


def _policy_configured() -> bool:
    """True when SOMEONE chose a policy (use() context or set_default).

    The tuning table may only fill silence: an author's explicit choice —
    per-call, scoped, or process-wide — always wins. The process default
    is "configured" exactly when it is no longer the DEFAULT_POLICY
    object set_default started from (identity, not equality: installing
    an equal-valued policy is still an explicit choice)."""
    ctx = _active.get()
    if ctx is not None and ctx[1] is not None:
        return True
    return _default[1] is not DEFAULT_POLICY


def _tuned_policy(op: str, *, bits: int,
                  shape) -> ExecutionPolicy | None:
    """Active tuning-table policy for this call, or None. Never raises."""
    try:
        from repro.tune import table as _table
    except Exception:  # pragma: no cover - tune ships with the package
        return None
    return _table.dispatch_policy(op, bits=bits, shape=shape)


def resolve(op: str, *, backend: str | Backend | None = None,
            policy: ExecutionPolicy | None = None,
            s: int = 1, t: int = 1, shape=None,
            tuned: bool = True) -> tuple[Backend, ExecutionPolicy]:
    """Pick the backend+policy for one op call.

    Explicit ``backend=`` pins the engine (raises if it can't run the op);
    otherwise the active context backend is used, falling back across the
    registry in registration order when it lacks the capability.

    Policy fallback chain (docs/tuning.md): explicit ``policy=`` > active
    ``use()`` context / ``set_default`` > active tuning-table entry
    (nearest (op, bits, shape) bucket; only when ``tuned`` and no policy
    was configured anywhere) > DEFAULT_POLICY. ``shape`` is the (m, k, n)
    hint for the table lookup; dispatchers that carry precomputed tile
    artifacts pass ``tuned=False`` — the artifacts were built on a
    specific tile grid, and a table policy must not swap the grid under
    them.
    """
    cur_be, cur_pol = current()
    pol = policy if policy is not None else cur_pol
    if policy is None and tuned and not _policy_configured():
        tpol = _tuned_policy(op, bits=max(s, t), shape=shape)
        if tpol is not None:
            pol = tpol
    if backend is not None:
        be = get_backend(backend)
        if not be.supports(op, s=s, t=t):
            raise UnsupportedOpError(
                f"backend {be.name!r} does not support {op} "
                f"with s={s}, t={t} (capabilities: {sorted(be.capabilities)})")
        return be, pol
    if cur_be.supports(op, s=s, t=t):
        return cur_be, pol
    for name in _ORDER:
        cand = _REGISTRY[name]
        if cand.supports(op, s=s, t=t):
            key = (cur_be.name, op, name)
            if key not in _warned_fallbacks:
                _warned_fallbacks.add(key)
                warnings.warn(
                    f"backend {cur_be.name!r} does not support {op}; "
                    f"falling back to {name!r}", RuntimeWarning, stacklevel=3)
            return cand, pol
    raise UnsupportedOpError(
        f"no registered backend supports {op} with s={s}, t={t} "
        f"(registered: {sorted(_REGISTRY)})")
