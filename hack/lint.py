#!/usr/bin/env python
"""AST lint gate for `make verify`.

reference: Makefile:25-38 — the reference's verify runs go vet +
golangci-lint and its battletest gates on gocyclo <= 10. This image ships
no ruff/pyflakes and installs are forbidden, so the same spirit is
enforced with the stdlib ast module:

  * cyclomatic complexity bound per function (branches + bool ops),
  * unused imports (module scope and function scope),
  * duplicated keys in dict literals,
  * mutable default arguments.

Scope is deliberately small and high-signal: every rule here is either
the reference's own gate (complexity) or a defect class that has no
legitimate instance in this codebase. Exceptions are declared inline
with `# lint: allow-complexity` on the def line for solver kernels whose
branch count is shape-unrolled math, not control-flow soup.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

MAX_COMPLEXITY = 10  # the reference's gocyclo gate (Makefile:25-31)

CHECK_ROOTS = (
    "karpenter_tpu",
    "tests",
    "hack",  # the gate checks itself
    "__graft_entry__.py",
)


def iter_files(root: Path):
    for entry in CHECK_ROOTS:
        path = root / entry
        if path.is_file():
            yield path
        else:
            yield from sorted(path.rglob("*.py"))


def _own_nodes(fn: ast.AST):
    """Walk a function's body WITHOUT descending into nested defs: each
    def is scored standalone (billing a closure's branches to its parent
    would double-count and force waivers on functions whose own control
    flow is simple)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            stack.extend(ast.iter_child_nodes(node))


def complexity(fn: ast.AST) -> int:
    """gocyclo-style: 1 + one per branch point (gocyclo counts if/for/
    case/&&/||; with/assert are not branches and are not counted)."""
    count = 1
    for node in _own_nodes(fn):
        if isinstance(
            node,
            (
                ast.If,
                ast.For,
                ast.AsyncFor,
                ast.While,
                ast.ExceptHandler,
                ast.IfExp,
            ),
        ):
            count += 1
        elif isinstance(node, ast.BoolOp):
            count += len(node.values) - 1
        elif isinstance(node, (ast.comprehension,)):
            count += 1 + len(node.ifs)
        elif isinstance(node, ast.Match):
            count += len(node.cases)
    return count


def _allowed(fn: ast.AST, source_lines) -> bool:
    line = source_lines[fn.lineno - 1]
    return "lint: allow-complexity" in line


class ImportTracker(ast.NodeVisitor):
    """Unused imports per scope (module + each function).

    Exemptions, matching pyflakes/ruff conventions: `from __future__`
    (a directive, not a binding), any import line carrying a `noqa`
    comment (the codebase's marker for side-effect imports that
    register providers/algorithms), and __init__.py files entirely
    (re-exports ARE the public API surface there).
    """

    def __init__(self, source_lines):
        self.problems = []
        self._lines = source_lines
        self._scopes = [{}]  # name -> (lineno, display)

    def _bind(self, name: str, lineno: int, display: str):
        if "noqa" in self._lines[lineno - 1]:
            return
        root = name.split(".")[0]
        self._scopes[-1][root] = (lineno, display)

    def visit_Import(self, node: ast.Import):
        for alias in node.names:
            self._bind(alias.asname or alias.name, node.lineno, alias.name)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module == "__future__":
            return
        for alias in node.names:
            if alias.name == "*":
                continue
            self._bind(alias.asname or alias.name, node.lineno, alias.name)

    def _walk_scope(self, node):
        self._scopes.append({})
        self.generic_visit(node)
        scope = self._scopes.pop()
        body_names = _used_names(node)
        for name, (lineno, display) in scope.items():
            if name not in body_names:
                self.problems.append((lineno, f"unused import: {display}"))

    visit_FunctionDef = _walk_scope
    visit_AsyncFunctionDef = _walk_scope

    def finish(self, tree: ast.Module):
        used = _used_names(tree)
        for name, (lineno, display) in self._scopes[0].items():
            if name not in used:
                self.problems.append((lineno, f"unused import: {display}"))


def _names_in_string(text: str, used: set) -> None:
    """Quoted forward references ("Optional[int]") hide names in
    strings; parse plausible ones so valid code never fails the gate
    (__all__ strings get counted too — acceptable under-reporting,
    never a false positive)."""
    text = text.strip()
    if not text or len(text) >= 200 or "\n" in text:
        return
    try:
        for sub in ast.walk(ast.parse(text, mode="eval")):
            if isinstance(sub, ast.Name):
                used.add(sub.id)
    except (SyntaxError, ValueError):
        pass


def _used_names(tree) -> set:
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            # "a.b.c" marks a used
            inner = node
            while isinstance(inner, ast.Attribute):
                inner = inner.value
            if isinstance(inner, ast.Name):
                used.add(inner.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            _names_in_string(node.value, used)
    return used


def _check_function(node, lines, is_test: bool, problems: list) -> None:
    score = complexity(node)
    # tests are exempt from the complexity bound (the reference gates
    # gocyclo over pkg/, not its test trees); every other rule still
    # applies to them
    if score > MAX_COMPLEXITY and not is_test and not _allowed(node, lines):
        problems.append(
            (
                node.lineno,
                f"{node.name} complexity {score} > "
                f"{MAX_COMPLEXITY} (split it, or annotate "
                "`# lint: allow-complexity` with a reason)",
            )
        )
    for default in node.args.defaults + node.args.kw_defaults:
        if isinstance(default, (ast.List, ast.Dict, ast.Set)):
            problems.append(
                (node.lineno, f"{node.name}: mutable default argument")
            )


def _check_dict_keys(node, problems: list) -> None:
    seen = set()
    for key in node.keys:
        # ast constant keys are always hashable (str/num/bytes/
        # None/bool); tuples parse as ast.Tuple, not Constant
        if isinstance(key, ast.Constant):
            if key.value in seen:
                problems.append(
                    (key.lineno, f"duplicate dict key {key.value!r}")
                )
            seen.add(key.value)


def check_file(path: Path):
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [(e.lineno or 0, f"syntax error: {e.msg}")]
    lines = source.splitlines()
    problems = []

    is_test = "tests" in path.parts
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _check_function(node, lines, is_test, problems)
        elif isinstance(node, ast.Dict):
            _check_dict_keys(node, problems)

    if path.name != "__init__.py":
        tracker = ImportTracker(lines)
        tracker.visit(tree)
        tracker.finish(tree)
        problems.extend(tracker.problems)
    return problems


def main() -> int:
    root = Path(__file__).resolve().parent.parent
    failures = 0
    for path in iter_files(root):
        for lineno, message in sorted(check_file(path)):
            print(f"{path.relative_to(root)}:{lineno}: {message}")
            failures += 1
    if failures:
        print(f"lint: {failures} problem(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
