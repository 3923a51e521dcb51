"""residency-pairing: class table ↔ kernel dispatch table symmetry.

Device residency invariant (exec/residency): the planner picks a
representation class per leaf stack at plan time and then dispatches
class-specific kernels by ``(class, op)`` lookup. A class registered
in ``REPR_CLASSES`` without a kernel variant for every op the dense
class supports is a latent plan-time KeyError — it only fires when a
query shape first routes that op at that class, i.e. in production,
not in the unit tests that exercised the class's happy path. The
reference has the same pairing discipline in its container classes
(roaring.go: every container type implements every op in the
binary-op matrix); this rule keeps the HBM port honest as classes are
added.

Checked, per module that declares BOTH tables at top level:

* every class in ``REPR_CLASSES`` registers every op the dense class
  registers (the dense row of the matrix is the contract);
* every class appearing in a ``KERNELS`` key is declared in
  ``REPR_CLASSES`` — an undeclared class is unreachable by the
  planner's policy and its kernels are dead weight (usually a typo'd
  constant);
* no ``KERNELS`` entry maps to a literal ``None`` — a ``None`` stub
  satisfies the pairing contract on paper while handing the planner a
  non-callable, which converts the loud plan-time KeyError this rule
  exists to prevent into a confusing TypeError deep inside a traced
  program (the hll row grew this way: each sketch op must point at a
  real kernel in pilosa_tpu/sketch/kernels.py, never a placeholder);
* no ``(class, op)`` key appears twice in the ``KERNELS`` literal — a
  duplicate key is legal Python (the last binding silently wins), so a
  copy-pasted row that re-registers an existing pair shadows the
  earlier kernel without any error, and the pairing check above still
  passes. Grew teeth with the keyplane row: four classes × four ops of
  near-identical lines is exactly where a pasted row keeps its old
  class constant.
"""

from __future__ import annotations

import ast
from collections.abc import Mapping

from pilosa_tpu.analysis.engine import Finding, ModuleInfo

RULE = "residency-pairing"

#: module path fragments this rule applies to (device kernel tables
#: live in the exec layer).
SCOPE_DIRS = ("exec/",)

#: the contract row of the kernel matrix: every other class must
#: support exactly the ops this class supports.
BASELINE_CLASS = "dense"


def _in_scope(path: str) -> bool:
    return any(f"/{d}" in path or path.startswith(d) for d in SCOPE_DIRS)


def _const_env(tree: ast.Module) -> dict[str, str]:
    """Top-level ``NAME = "literal"`` string bindings, for resolving
    class names spelled as constants in the tables."""
    env: dict[str, str] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            env[node.targets[0].id] = node.value.value
    return env


def _resolve(node: ast.expr, env: dict[str, str]) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return env.get(node.id)
    return None


def _top_assign(tree: ast.Module, name: str) -> ast.Assign | None:
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name):
            return node
    return None


def check(mod: ModuleInfo, project: Mapping[str, ModuleInfo]) -> list[Finding]:
    if not _in_scope(mod.path):
        return []
    classes_node = _top_assign(mod.tree, "REPR_CLASSES")
    kernels_node = _top_assign(mod.tree, "KERNELS")
    if classes_node is None or kernels_node is None:
        return []  # not a residency table module
    env = _const_env(mod.tree)

    classes: list[str] = []
    if isinstance(classes_node.value, (ast.Tuple, ast.List)):
        for el in classes_node.value.elts:
            name = _resolve(el, env)
            if name is not None:
                classes.append(name)

    # (class, op) pairs actually registered in the dispatch dict.
    table: dict[str, set[str]] = {}
    stubs: list[tuple[str, str, int]] = []
    dups: list[tuple[str, str, int]] = []
    if isinstance(kernels_node.value, ast.Dict):
        for key, value in zip(kernels_node.value.keys,
                              kernels_node.value.values):
            if not (isinstance(key, ast.Tuple) and len(key.elts) == 2):
                continue
            klass = _resolve(key.elts[0], env)
            op = _resolve(key.elts[1], env)
            if klass is not None and op is not None:
                if op in table.get(klass, ()):
                    dups.append((klass, op, key.lineno))
                table.setdefault(klass, set()).add(op)
                if (isinstance(value, ast.Constant)
                        and value.value is None):
                    stubs.append((klass, op, value.lineno))

    findings: list[Finding] = []
    for klass, op, lineno in dups:
        findings.append(Finding(
            RULE, mod.path, lineno,
            f"KERNELS registers ({klass!r}, {op!r}) more than once — "
            f"Python keeps the LAST binding silently, so this entry "
            f"shadows an earlier kernel (copy-pasted row with a stale "
            f"class constant?)"))
    for klass, op, lineno in stubs:
        findings.append(Finding(
            RULE, mod.path, lineno,
            f"KERNELS entry ({klass!r}, {op!r}) maps to a literal None "
            f"stub — it satisfies the pairing contract but dispatches a "
            f"non-callable, turning the plan-time KeyError this rule "
            f"prevents into a TypeError inside a traced program"))
    baseline = table.get(BASELINE_CLASS)
    if baseline:
        for klass in classes:
            if klass == BASELINE_CLASS:
                continue
            missing = sorted(baseline - table.get(klass, set()))
            if missing:
                findings.append(Finding(
                    RULE, mod.path, kernels_node.lineno,
                    f"representation class {klass!r} registers no kernel "
                    f"variant for op(s) {', '.join(missing)} the "
                    f"{BASELINE_CLASS!r} class supports — a plan that "
                    f"routes that op at this class raises at plan time"))
    for klass in sorted(table):
        if klass not in classes:
            findings.append(Finding(
                RULE, mod.path, kernels_node.lineno,
                f"KERNELS registers class {klass!r} which is not "
                f"declared in REPR_CLASSES — unreachable by the "
                f"planner's class policy (typo'd constant?)"))
    return findings
