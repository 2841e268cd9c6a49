"""Pass 4 — the Pallas kernel contract (ABC4xx).

Every kernel package under ``src/repro/kernels/`` serves three impls
behind one dispatcher (``ops.py``): the TPU Pallas kernel (``kernel.py``),
its interpret-mode execution, and the pure-XLA fallback, with ``ref.py``
as the parity oracle.  PR 4's flash ``q_offset`` fix is the bug class:
a dispatcher that bare-``assert``s its preconditions crashes opaque under
``python -O`` silently passes them.  This pass freezes the contract:

ABC401  a kernel package missing the ops/kernel/ref trio (project check).
ABC402  retired (it routed compiler params through a jax-version shim;
        the repo pins one jax, and kernels use ``pltpu.CompilerParams``).
ABC403  ``pl.pallas_call`` without an ``interpret=`` kwarg: the kernel
        body would be TPU-only, untestable in CI.
ABC404  bare ``assert`` in a dispatcher (``ops.py``) or
        ``kernels/config.py`` — preconditions must raise typed errors
        carrying the offending shapes (``python -O`` deletes asserts).
ABC405  a function that launches ``pl.pallas_call`` without a block-
        divisibility guard (an ``assert``/``raise`` on a ``%`` test):
        BlockSpec tiling silently mis-indexes when shapes don't divide.
"""
from __future__ import annotations

import ast
import os
from typing import List

from tools.abclint import astutil
from tools.abclint.engine import FileContext, Finding, Pass

RULES = {
    "ABC401": "kernel package missing the ops.py/kernel.py/ref.py trio",
    "ABC403": "pl.pallas_call without an interpret= kwarg (kernel body "
              "untestable off-TPU)",
    "ABC404": "bare assert in a kernel dispatcher (raise a typed error "
              "carrying the offending shapes)",
    "ABC405": "pallas_call launch without a BlockSpec divisibility guard",
}

_TRIO = ("ops.py", "kernel.py", "ref.py")


def in_scope(relpath: str) -> bool:
    return relpath.startswith("src/repro/kernels/")


def _is_dispatcher(relpath: str) -> bool:
    return relpath.endswith("/ops.py") or relpath.endswith("kernels/config.py")


def _has_mod_guard(fn: ast.AST) -> bool:
    """An assert or a raise-under-if whose test involves ``%``."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Assert):
            if any(isinstance(b.op, ast.Mod)
                   for b in ast.walk(node.test) if isinstance(b, ast.BinOp)):
                return True
        if isinstance(node, ast.If):
            has_mod = any(
                isinstance(b.op, ast.Mod)
                for b in ast.walk(node.test) if isinstance(b, ast.BinOp)
            )
            has_raise = any(
                isinstance(n, ast.Raise) for n in ast.walk(node)
            )
            if has_mod and has_raise:
                return True
    return False


def check_file(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    for node, stack in astutil.enclosing_functions(ctx.tree):
        if isinstance(node, ast.Call):
            d = astutil.call_name(node)
            if d is not None and d.split(".")[-1] == "pallas_call":
                kwargs = {k.arg for k in node.keywords}
                if "interpret" not in kwargs and None not in kwargs:
                    findings.append(
                        ctx.finding(
                            "ABC403", node,
                            "pl.pallas_call without interpret= — thread "
                            "an interpret flag through so the kernel body "
                            "runs in CI",
                        )
                    )
                fn = stack[-1] if stack else None
                if fn is not None and not _has_mod_guard(fn):
                    findings.append(
                        ctx.finding(
                            "ABC405", node,
                            f"{getattr(fn, 'name', '<lambda>')}() launches "
                            "pallas_call without a block-divisibility "
                            "guard — BlockSpec tiling mis-indexes on "
                            "non-dividing shapes; raise on `dim % block`",
                        )
                    )
        if isinstance(node, ast.Assert) and _is_dispatcher(ctx.path):
            findings.append(
                ctx.finding(
                    "ABC404", node,
                    "bare assert in a dispatcher — python -O deletes it "
                    "and the failure message hides the shapes; raise "
                    "ValueError carrying the offending values",
                )
            )
    return findings


def check_project(root: str) -> List[Finding]:
    findings: List[Finding] = []
    kroot = os.path.join(root, "src", "repro", "kernels")
    if not os.path.isdir(kroot):
        return findings
    for name in sorted(os.listdir(kroot)):
        pkg = os.path.join(kroot, name)
        if not os.path.isdir(pkg) or name == "__pycache__":
            continue
        missing = [f for f in _TRIO if not os.path.isfile(os.path.join(pkg, f))]
        if missing:
            findings.append(
                Finding(
                    "ABC401",
                    f"src/repro/kernels/{name}",
                    0,
                    f"kernel package missing {', '.join(missing)} — every "
                    "kernel ships the dispatcher/kernel/reference trio "
                    "(DESIGN.md §4)",
                    snippet=name,
                )
            )
    return findings


PASS = Pass(
    name="kernel_contract",
    rules=RULES,
    check_file=check_file,
    check_project=check_project,
    scope=in_scope,
)
