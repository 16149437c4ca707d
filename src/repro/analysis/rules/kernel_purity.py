"""kernel-int-purity: no float math inside the integer kernel modules.

QGTC's claim is a BIT-EXACT integer path: bit-plane popcount GEMMs whose
accumulators, tiles and outputs are int32 end to end.  A float dtype
sneaking into ``kernels/bitserial.py``/``sgt.py``/``ops.py``
silently breaks exactness (rounding) and, on real hardware, knocks the
kernel off the integer tensor-core path.  The ONE sanctioned exception is
the §4.5 fused-requantize epilogue (alpha/beta rescale + clip), which is
float BY DESIGN — those functions carry a ``# lint: allow[kernel-int-purity]``
waiver on their ``def`` line, and the abstract-trace checker
(repro.analysis.trace) independently proves the float ops never reach a
``dot_general``.

``bitpack.py`` (float -> int quantization), ``wqmm.py`` (weight-only
matmul with float activations) and ``ref.py`` (reference oracle) are float
by contract and out of scope.
"""
from __future__ import annotations

import ast
import re

from repro.analysis.engine import Rule

_SCOPE = re.compile(r"(^|/)repro/kernels/(bitserial|sgt|ops)\.py$")

_FLOAT_DTYPES = {"float32", "float64", "float16", "bfloat16", "float_"}
# elementwise float producers/consumers that have no business in an
# integer GEMM body (outside a waived epilogue)
_FLOAT_FNS = {"floor", "ceil", "exp", "log", "log2", "sqrt", "rsqrt",
              "tanh", "sigmoid", "softmax", "sin", "cos"}
_ARRAY_NS = {"jnp", "np", "numpy", "lax", "jax"}


def _ns_of(node):
    """Leftmost Name id of an attribute chain (``jnp`` of ``jnp.floor``)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


class KernelIntPurity(Rule):
    name = "kernel-int-purity"
    description = ("no float dtypes, float literals, astype(float) or "
                   "float elementwise ops inside the integer kernel "
                   "modules (kernels/{bitserial,sgt,ops}.py); the "
                   "fused §4.5 epilogue is waived explicitly")

    def applies_to(self, path: str) -> bool:
        return bool(_SCOPE.search(path))

    def check(self, path, tree, lines):
        out = []
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr in _FLOAT_DTYPES
                    and _ns_of(node) in _ARRAY_NS):
                out.append(self.finding(
                    path, node,
                    f"float dtype {_ns_of(node)}.{node.attr} in an integer "
                    f"kernel module (bit-exact int32 path required)"))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr == "astype"
                  and node.args
                  and isinstance(node.args[0], ast.Constant)
                  and isinstance(node.args[0].value, str)
                  and node.args[0].value in _FLOAT_DTYPES):
                out.append(self.finding(
                    path, node,
                    f"astype({node.args[0].value!r}) in an integer kernel "
                    f"module"))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id == "float"):
                out.append(self.finding(
                    path, node,
                    "builtin float(...) in an integer kernel module"))
            elif (isinstance(node, ast.Constant)
                  and type(node.value) is float):
                out.append(self.finding(
                    path, node,
                    f"float literal {node.value!r} in an integer kernel "
                    f"module"))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr in _FLOAT_FNS
                  and _ns_of(node.func) in _ARRAY_NS):
                out.append(self.finding(
                    path, node,
                    f"float elementwise op "
                    f"{_ns_of(node.func)}.{node.func.attr} in an integer "
                    f"kernel module"))
        return out
