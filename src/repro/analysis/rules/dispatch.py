"""api-dispatch-bypass: kernel execution goes through repro.api only.

The dispatch layer (repro/api) owns everything a raw kernel call would
silently skip: backend capability probing, ``tiles=`` stripping for
backends without zero-tile jumping, the explicit-policy > use() >
tuning-table > DEFAULT_POLICY resolution chain, and host-scalar
validation.  A ``from repro.kernels import ops`` outside ``kernels/`` /
``api/`` reaches around all of that — it pins one backend, ignores the
installed tuning table, and breaks the moment the capability matrix
changes (exactly what PR 7's sparse-translation backends did).

Exempt kernel modules: ``repro.kernels.sgt`` and ``repro.kernels.ref``.
They are not execution paths — sgt builds translation ARTIFACTS (the
word-condensed column remap consumed via ``tiles=``, which serve/engine
and tune/sweep legitimately precompute), and ref is the pure-Python
oracle tests compare against.
"""
from __future__ import annotations

import ast
import re

from repro.analysis.engine import Rule

_EXEMPT = re.compile(r"(^|/)(repro/(kernels|api)/|tests/)")
_EXEC_MODULES = {"ops", "bitserial", "bitpack", "wqmm"}


class DispatchBypass(Rule):
    name = "api-dispatch-bypass"
    description = ("no direct import of the kernel execution modules "
                   "(repro.kernels.{ops,bitserial,bitpack,wqmm}) "
                   "outside kernels/ and api/ — dispatch through repro.api; "
                   "artifact/oracle modules (kernels.sgt, kernels.ref) are "
                   "exempt")

    def applies_to(self, path: str) -> bool:
        return path.endswith(".py") and not _EXEMPT.search(path)

    def _bad(self, path, node, mod):
        return self.finding(
            path, node,
            f"direct import of repro.kernels.{mod} bypasses repro.api "
            f"dispatch (backend probing, tiles= capability stripping, "
            f"policy/tuning-table resolution); call the repro.api "
            f"dispatcher with an explicit backend/policy instead")

    def check(self, path, tree, lines):
        out = []
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                if node.module == "repro.kernels":
                    for a in node.names:
                        if a.name in _EXEC_MODULES:
                            out.append(self._bad(path, node, a.name))
                elif node.module and node.module.startswith("repro.kernels."):
                    mod = node.module.split(".")[2]
                    if mod in _EXEC_MODULES:
                        out.append(self._bad(path, node, mod))
            elif isinstance(node, ast.Import):
                for a in node.names:
                    parts = a.name.split(".")
                    if (parts[:2] == ["repro", "kernels"] and len(parts) > 2
                            and parts[2] in _EXEC_MODULES):
                        out.append(self._bad(path, node, parts[2]))
        return out
