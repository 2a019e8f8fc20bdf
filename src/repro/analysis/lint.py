"""Convention lint pass: AST checks for rules ruff cannot express.

Three repo rules, each with a comment-waiver escape hatch (``# contract:
allow-<rule>`` on the offending line or the line above -- a waiver is a
reviewed, documented exception, not a hole):

* raw-collective: ``jax.lax.psum``/``pmax``/... and ``shard_map`` may be
  CALLED only in ``core/engine.py`` (the solvers' single communication
  point, ``_packet_reduce``).  Anything else either routes through the
  engine or carries an ``allow-collective`` waiver.
* operand-transpose: inside classes that implement the Formulation/bound
  hooks (``bind``/``bind_shard``/``packet_vector``/``update``/
  ``inner_sweep``/``init_carry``/``metrics``), no ``.T`` -- the PR-5 rule
  that operands bind in their ORIGINAL layout and all transposition lives
  in the PacketOperand gather strategy.  Warm-start/metrics uses carry
  ``allow-transpose`` waivers.
* env-before-jax: a module that sets ``os.environ["XLA_FLAGS"]`` at module
  level must do so BEFORE its first module-level jax import -- after the
  backend initializes, the flag is read-once dead (device counts silently
  wrong, the classic 1-device "distributed" test).

Pure stdlib (ast + tokenize-free line scan): runs without jax installed,
which keeps ``python -m repro.analysis lint`` usable as a pre-commit hook.
"""
from __future__ import annotations

import ast
import os

from .report import PassReport, Violation

COLLECTIVE_CALLS = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "psum_scatter",
    "ppermute", "all_to_all"})
# Files where raw collectives ARE the design (path suffixes, POSIX form).
COLLECTIVE_ALLOWLIST = ("repro/core/engine.py",)
# A class is "formulation-shaped" if it defines any of these hooks.
FORMULATION_HOOKS = frozenset({
    "bind", "bind_shard", "packet_vector", "update", "inner_sweep",
    "init_carry", "metrics", "dist_in_specs"})
DEFAULT_ROOTS = ("src/repro", "scripts", "examples", "benchmarks")


def _waived(lines: list, lineno: int, rule: str) -> bool:
    """Waiver on the offending line, or anywhere in the contiguous comment
    block immediately above it (waivers read best as a short explanation)."""
    tag = f"contract: allow-{rule}"
    if 1 <= lineno <= len(lines) and tag in lines[lineno - 1]:
        return True
    ln = lineno - 1
    while 1 <= ln <= len(lines) and lines[ln - 1].lstrip().startswith("#"):
        if tag in lines[ln - 1]:
            return True
        ln -= 1
    return False


def _attr_chain(node) -> list:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts[::-1]


def _is_collective_call(call: ast.Call) -> str | None:
    chain = _attr_chain(call.func)
    if not chain:
        return None
    if chain[-1] == "shard_map":
        return "shard_map"
    if chain[-1] in COLLECTIVE_CALLS and "lax" in chain[:-1]:
        return ".".join(chain)
    return None


def _check_collectives(tree, lines, relpath, violations):
    if relpath.endswith(COLLECTIVE_ALLOWLIST):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _is_collective_call(node)
        if name and not _waived(lines, node.lineno, "collective"):
            violations.append(Violation(
                "raw-collective", f"{relpath}:{node.lineno}",
                f"raw {name} call outside core/engine.py -- route the "
                "reduction through the engine's packet, or waive with "
                "'# contract: allow-collective'"))


def _check_transposes(tree, lines, relpath, violations):
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = {n.name for n in cls.body if isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        if not methods & FORMULATION_HOOKS:
            continue
        for node in ast.walk(cls):
            if (isinstance(node, ast.Attribute) and node.attr == "T"
                    and not _waived(lines, node.lineno, "transpose")):
                violations.append(Violation(
                    "operand-transpose", f"{relpath}:{node.lineno}",
                    f"'.T' inside formulation class {cls.name} -- operands "
                    "bind in their original layout (the PacketOperand owns "
                    "the gather); waive with '# contract: allow-transpose'"))


def _is_jax_import(node) -> bool:
    if isinstance(node, ast.Import):
        return any(a.name == "jax" or a.name.startswith("jax.")
                   for a in node.names)
    if isinstance(node, ast.ImportFrom):
        mod = node.module or ""
        return mod == "jax" or mod.startswith("jax.")
    return False


def _xla_flags_lineno(node) -> int | None:
    """Module-level statement that writes os.environ['XLA_FLAGS'] (assign or
    .setdefault), else None."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Subscript):
            if (_attr_chain(sub.value)[-2:] == ["os", "environ"]
                    or _attr_chain(sub.value) == ["environ"]):
                key = sub.slice
                if isinstance(key, ast.Constant) and key.value == "XLA_FLAGS":
                    if isinstance(getattr(sub, "ctx", None), ast.Store):
                        return sub.lineno
        if isinstance(sub, ast.Call):
            chain = _attr_chain(sub.func)
            if chain[-1:] == ["setdefault"] and "environ" in chain:
                if (sub.args and isinstance(sub.args[0], ast.Constant)
                        and sub.args[0].value == "XLA_FLAGS"):
                    return sub.lineno
    return None


def _check_env_order(tree, lines, relpath, violations):
    first_jax = None
    for node in tree.body:  # module level only: function bodies run later
        if first_jax is None and _is_jax_import(node):
            first_jax = node.lineno
        ln = _xla_flags_lineno(node)
        if ln is not None and first_jax is not None:
            if not _waived(lines, ln, "env-order"):
                violations.append(Violation(
                    "env-before-jax", f"{relpath}:{ln}",
                    f"XLA_FLAGS set after 'import jax' (line {first_jax}) "
                    "-- the backend has already initialized, the flag is "
                    "dead; set it before the import"))


def iter_py_files(paths):
    for path in paths:
        if os.path.isfile(path):
            yield path
            continue
        for root, dirs, files in os.walk(path):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".") and d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def lint_file(path: str, repo_root: str | None = None) -> list:
    relpath = os.path.relpath(path, repo_root) if repo_root else path
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Violation("parse-error", f"{relpath}:{e.lineno}", str(e))]
    lines = src.splitlines()
    violations: list = []
    _check_collectives(tree, lines, relpath, violations)
    _check_transposes(tree, lines, relpath, violations)
    _check_env_order(tree, lines, relpath, violations)
    return violations


def run_lint(paths=None, repo_root: str | None = None) -> PassReport:
    """Lint the given files/trees (default: the repo's source trees)."""
    if paths is None:
        root = repo_root or os.getcwd()
        paths = [os.path.join(root, p) for p in DEFAULT_ROOTS
                 if os.path.exists(os.path.join(root, p))]
    rep = PassReport("lint")
    for path in iter_py_files(paths):
        rep.case(os.path.relpath(path, repo_root) if repo_root else path)
        rep.violations.extend(lint_file(path, repo_root))
    return rep
