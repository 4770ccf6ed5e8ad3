"""jit-raw / jit-family / pallas-raw / jit-device-sync: the `global_jit`
discipline.

Every perf PR re-proves the same invariants with dispatch-count guards;
these passes mechanize them:

- **jit-raw**: a program jitted OUTSIDE a builder passed to `global_jit` is
  invisible to the process-wide LRU (no cross-execution reuse, no
  compile-span accounting, no retrace counting) — a plan-cache hit would
  still pay a full retrace.  `jit_program(...)` is legal only inside a
  function whose name is passed to `global_jit` in the same module (the
  `def build(): ... return jit_program(run)` idiom) or in a lambda written
  directly into a `global_jit(...)` argument.  A bare `jax.jit(...)` is
  refused everywhere, builders included: its HLO module would be named after
  the closure (`jit_run`, `jit_spmd`), and a device profile could not tell
  one operator's program from another's; `jit_program` names it after the
  key's family.
- **jit-family**: a program's family is the first element of its
  `global_jit` key (`jit_program` names the HLO module after it, and the
  benchmark's table of families, `benchmarks/harness/spans.py`, groups device
  time by it), so it must be readable from the source: a string literal heading
  a tuple literal, written into the call or assigned to the name passed.
  `program_families()` is that reading; `tests/test_program_names.py` holds
  the table equal to it.
- **pallas-raw**: `pl.pallas_call(...)` constructs a kernel program with the
  exact same escape hazard — same rule shape: legal only inside a
  `global_jit` builder, so Pallas kernels are cached per static shape and
  counted like every other program.
- **jit-device-sync**: `.item()` / `.block_until_ready()` on the default
  query path forces a host<->device sync per call.  Flagged in the hot-path
  layers (exec/, kernels/, parallel/, chunk/, server/, storage/) unless the
  enclosing scope is profiling/bench/EXPLAIN machinery (allowlisted by
  qualname pattern), where the sync is the point.
"""

from __future__ import annotations

import ast
import re
from typing import List, Set

from galaxysql_tpu.devtools.lint import Checker, Module

HOT_PREFIXES = ("galaxysql_tpu/exec/", "galaxysql_tpu/kernels/",
                "galaxysql_tpu/parallel/", "galaxysql_tpu/chunk/",
                "galaxysql_tpu/server/", "galaxysql_tpu/storage/")

# scopes where a device sync is the feature, not a leak: profiling, EXPLAIN
# ANALYZE, benchmarks, tracing/telemetry observation hooks
ALLOW_QUAL = re.compile(
    r"explain|profil|bench|analyz|stats|trace|observe|debug|telemetry",
    re.IGNORECASE)


def _is_jax_jit(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "jit"
            and isinstance(f.value, ast.Name) and f.value.id == "jax")


def _is_jit_program(call: ast.Call) -> bool:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id == "jit_program"
    return isinstance(f, ast.Attribute) and f.attr == "jit_program"


def _is_pallas_call(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "pallas_call"
            and isinstance(f.value, ast.Name) and f.value.id == "pl")


def _is_global_jit(call: ast.Call) -> bool:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id == "global_jit"
    return isinstance(f, ast.Attribute) and f.attr == "global_jit"


def _key_head(expr: ast.AST):
    """The first element of a key expression: a tuple literal, or a tuple
    literal with more appended (`(head, ...) + rest`)."""
    while isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        expr = expr.left
    if isinstance(expr, ast.Tuple) and expr.elts:
        return expr.elts[0]
    return None


def program_families(tree: ast.AST):
    """`(families, unreadable)`: the family of every `global_jit` call's key in
    `tree`, as `exec.operators.program_family` would give it, and the line
    numbers of calls whose key's head is no string literal.  A fused segment's
    key starts with its backend (a name, `exec/fusion.py`): family `segment`."""
    families: Set[str] = set()
    unreadable: List[int] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        assigned = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                assigned.setdefault(node.targets[0].id, []).append(node.value)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call) and _is_global_jit(node)
                    and node.args):
                continue
            key = node.args[0]
            # a name never assigned here is a parameter: its caller's to read
            heads = [_key_head(v) for v in assigned.get(key.id, [])] \
                if isinstance(key, ast.Name) else [_key_head(key)]
            for head in heads:
                if isinstance(head, ast.Constant) and isinstance(head.value,
                                                                 str):
                    families.add(head.value.replace("-", "_"))
                elif isinstance(head, ast.Name) and head.id == "backend":
                    families.add("segment")
                else:
                    unreadable.append(node.lineno)
    return families, sorted(set(unreadable))


class JitDisciplineChecker(Checker):
    rules = ("jit-raw", "jit-family", "pallas-raw", "jit-device-sync")
    description = ("bare jax.jit anywhere, jit_program / pl.pallas_call "
                   "outside a global_jit builder closure; device-sync "
                   "primitives on the hot path "
                   "outside profiling/bench scopes")

    def check(self, mod: Module):
        findings = []
        findings.extend(self._check_raw_jit(mod))
        for lineno in program_families(mod.tree)[1]:
            findings.append(self.finding(
                mod, lineno,
                "global_jit key whose first element is no string literal: "
                "the program's family (its HLO module's name, its group in "
                "a device profile) cannot be read from the source",
                rule="jit-family"))
        if mod.relpath.startswith(HOT_PREFIXES):
            findings.extend(self._check_device_sync(mod))
        return findings

    # -- jit-raw / pallas-raw ------------------------------------------------

    def _check_raw_jit(self, mod: Module):
        builder_names: Set[str] = set()
        allowed_lambdas: Set[int] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and _is_global_jit(node):
                args = list(node.args) + [kw.value for kw in node.keywords]
                for a in args:
                    if isinstance(a, ast.Name):
                        builder_names.add(a.id)
                for a in args:
                    for sub in ast.walk(a):
                        if isinstance(sub, ast.Lambda):
                            allowed_lambdas.add(id(sub))

        findings = []

        def in_builder(stack: List[ast.AST]) -> bool:
            for s in stack:
                if isinstance(s, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)) and \
                        s.name in builder_names:
                    return True
                if isinstance(s, ast.Lambda) and id(s) in allowed_lambdas:
                    return True
            return False

        def walk(node: ast.AST, stack: List[ast.AST]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Call) and _is_jax_jit(child):
                    findings.append(self.finding(
                        mod, child.lineno,
                        "bare jax.jit in a global_jit builder: its HLO "
                        "module is named after the closure (jit_run), so a "
                        "device profile cannot name the operator; call "
                        "jit_program" if in_builder(stack) else
                        "raw jax.jit outside a global_jit builder "
                        "closure: the program escapes the process-wide "
                        "LRU, retrace accounting, and compile spans",
                        rule="jit-raw"))
                if isinstance(child, ast.Call) and _is_jit_program(child) \
                        and not in_builder(stack):
                    findings.append(self.finding(
                        mod, child.lineno,
                        "jit_program outside a global_jit builder "
                        "closure: the program escapes the process-wide "
                        "LRU, retrace accounting, and compile spans",
                        rule="jit-raw"))
                if isinstance(child, ast.Call) and _is_pallas_call(child) \
                        and not in_builder(stack):
                    findings.append(self.finding(
                        mod, child.lineno,
                        "raw pl.pallas_call outside a global_jit builder "
                        "closure: the kernel program escapes the "
                        "process-wide LRU, retrace accounting, and compile "
                        "spans",
                        rule="pallas-raw"))
                walk(child, stack + [child])

        walk(mod.tree, [])
        return findings

    # -- jit-device-sync -----------------------------------------------------

    def _check_device_sync(self, mod: Module):
        findings = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if f.attr not in ("item", "block_until_ready"):
                continue
            qual = mod.qualname_at(node.lineno)
            if ALLOW_QUAL.search(qual or ""):
                continue
            findings.append(self.finding(
                mod, node.lineno,
                f".{f.attr}() forces a host<->device sync; on the default "
                f"query path every call stalls the dispatch pipeline "
                f"(profiling/bench scopes are allowlisted by name)",
                rule="jit-device-sync", severity="warn"))
        return findings
