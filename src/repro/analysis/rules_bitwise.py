"""TAO005 — fma-contraction hazard in bitwise-deterministic functions.

``core.features.signed_log`` (and its jax twin
``kernels.fused.ops.signed_log_device``) carry a contract the test
suite pins: the twin's output, eager or inside the compiled fused
extraction program, is **bit-identical** to the NumPy reference on the
CPU, which is why both are written as one-op-per-statement Horner steps.
XLA is free to contract ``a * b + c`` into an fma, whose
differently-rounded result breaks ``np.array_equal`` on exactly the
backends where it matters; the twin stops it by rounding each product
through an integer barrier (``bitcast(bitcast(p) | zero)``, ``zero``
traced inside a compiled program), which needs the product in its own
statement.  The hazard pattern is purely syntactic: an
``Add``/``Sub`` whose operand is a literal ``Mult`` expression.
Functions opt in with ``# tao: bitwise``; the fix is always the same —
hoist the product into its own statement.
"""
from __future__ import annotations

import ast
from typing import Iterator

from .core import Analysis, Finding, SourceFile, body_nodes, register_rule


def _is_mult(node: ast.AST) -> bool:
    return isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult)


@register_rule(
    "TAO005",
    "multiply fused into an add/sub inside a `# tao: bitwise` function "
    "(XLA may contract it into an fma and break NumPy bit-equality)",
)
def check_bitwise(sf: SourceFile, analysis: Analysis) -> Iterator[Finding]:
    for qual, fi in sorted(sf.funcs.items()):
        if not fi.bitwise:
            continue
        for node in body_nodes(fi.node):
            if not isinstance(node, ast.BinOp):
                continue
            if not isinstance(node.op, (ast.Add, ast.Sub)):
                continue
            if _is_mult(node.left) or _is_mult(node.right):
                op = "+" if isinstance(node.op, ast.Add) else "-"
                yield Finding(
                    sf.display, node.lineno, node.col_offset, "TAO005",
                    f"`a * b {op} c` shape in bitwise function `{qual}` — "
                    "XLA may fma-contract it; assign the product to its own "
                    "variable first (see core.features.signed_log)",
                )
