"""Engine-contract linter: AST checks for repo-wide rules (DESIGN.md §12).

The engine's performance and layering contracts are codebase properties, not
plan properties, so they can't live in the plan verifier.  This module lints
``src/`` with Python's ``ast`` — no imports of the linted code — against a
committed allowlist (``tools/lint_allowlist.json``):

* ``sync-call`` — no host-sync calls on the steady-state paths:
  ``jax.device_get`` / ``jax.block_until_ready`` (module or method form),
  ``.item()``, and ``float()`` / ``np.asarray()`` / ``np.array()`` wrapping
  a fresh ``jax``/``jnp`` call result.  The no-sync rule (DESIGN.md §11) is
  what keeps a tick one async dispatch; the allowlist names the few modules
  with *documented* sync points (ingest, checkpoint gather, autotune
  timing probes, snapshot export).
* ``obs-no-device`` — nothing under ``obs/`` may import ``jax``: telemetry
  must observe the engine without ever touching (and so never syncing)
  device values.
* ``engine-outside-core`` — ``Engine`` construction and ``compile`` /
  ``compile_incremental`` calls on it are ``core/``-internal; everything
  else goes through the session facade (``repro.connect`` → ``Database``),
  which is what lets the deprecation shims eventually be deleted.
* ``random-key`` — no ``jax.random.PRNGKey(<literal>)``: keys must thread
  in from config/args, or parallel runs silently share randomness.

Run via ``tools/lint_contracts.py`` (the CI entry point) or the installed
``repro-lint`` script.  Violations print the rule id, ``file:line:col``,
the message, and the allowlist remedy; the process exits non-zero if any
survive the allowlist.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence

RULES = ("sync-call", "obs-no-device", "engine-outside-core", "random-key")

#: documented per-rule remedies, rendered with each violation
_REMEDY = {
    "sync-call": ("hoist the sync off the steady-state path, or add the "
                  "file under \"sync-call\" in tools/lint_allowlist.json "
                  "with a reason documenting the sync point"),
    "obs-no-device": ("keep obs/ device-free (record host scalars the "
                      "caller already has); there is deliberately no "
                      "allowlist story for device work in telemetry"),
    "engine-outside-core": ("use repro.connect(...).views(...) instead of "
                            "constructing Engine directly, or add the file "
                            "under \"engine-outside-core\" in "
                            "tools/lint_allowlist.json with a reason"),
    "random-key": ("thread the key (or seed) in from config/arguments "
                   "instead of a literal PRNGKey, or add the file under "
                   "\"random-key\" in tools/lint_allowlist.json with a "
                   "reason"),
}


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str           # repo-relative posix path
    line: int
    col: int
    message: str

    def render(self) -> str:
        return (f"{self.rule}: {self.path}:{self.line}:{self.col}  "
                f"{self.message}\n    remedy: {_REMEDY[self.rule]}")


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chains as a dotted string (None otherwise)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Linter(ast.NodeVisitor):
    def __init__(self, rel: str):
        self.rel = rel
        self.in_obs = "obs" in Path(rel).parts
        self.in_core = "repro/core/" in rel
        self.violations: List[Violation] = []
        # local alias -> canonical dotted module/object name
        self.aliases: Dict[str, str] = {}
        # variables assigned from Engine(...) calls (any scope; linear and
        # flow-insensitive — good enough for a contract lint)
        self.engine_vars: set = set()

    def flag(self, node: ast.AST, rule: str, message: str) -> None:
        self.violations.append(Violation(rule, self.rel, node.lineno,
                                         node.col_offset, message))

    # -- alias tracking ------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.aliases[a.asname or a.name.split(".")[0]] = \
                a.name if a.asname else a.name.split(".")[0]
            if a.asname:
                self.aliases[a.asname] = a.name
        if self.in_obs:
            for a in node.names:
                if a.name == "jax" or a.name.startswith("jax."):
                    self.flag(node, "obs-no-device",
                              f"import {a.name}: obs/ must stay device-free "
                              "(the §11 no-sync telemetry rule)")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        for a in node.names:
            self.aliases[a.asname or a.name] = f"{mod}.{a.name}"
        if self.in_obs and (mod == "jax" or mod.startswith("jax.")):
            self.flag(node, "obs-no-device",
                      f"from {mod} import ...: obs/ must stay device-free "
                      "(the §11 no-sync telemetry rule)")
        self.generic_visit(node)

    def _canon(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of an expression through import aliases:
        ``jnp.sum`` -> ``jax.numpy.sum`` under ``import jax.numpy as jnp``."""
        dotted = _dotted(node)
        if dotted is None:
            return None
        head, _, rest = dotted.partition(".")
        head = self.aliases.get(head, head)
        return f"{head}.{rest}" if rest else head

    # -- assignments: track Engine(...) receivers ----------------------------

    def _note_engine_assign(self, targets, value) -> None:
        if not (isinstance(value, ast.Call)
                and self._is_engine_name(value.func)):
            return
        for t in targets:
            if isinstance(t, ast.Name):
                self.engine_vars.add(t.id)

    def visit_Assign(self, node: ast.Assign) -> None:
        self._note_engine_assign(node.targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._note_engine_assign([node.target], node.value)
        self.generic_visit(node)

    def _is_engine_name(self, func: ast.AST) -> bool:
        canon = self._canon(func)
        return canon in ("repro.core.Engine", "repro.core.engine.Engine")

    # -- call checks ---------------------------------------------------------

    def _contains_device_call(self, node: ast.AST) -> bool:
        """Whether a subtree calls into jax/jnp — the result is a freshly
        produced traced/device value, so host-converting it is a sync."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                canon = self._canon(sub.func) or ""
                if canon == "jax" or canon.startswith(("jax.", "jnp.")):
                    return True
        return False

    def visit_Call(self, node: ast.Call) -> None:
        canon = self._canon(node.func) or ""

        # sync-call: explicit jax host-sync entry points
        if canon in ("jax.device_get", "jax.block_until_ready"):
            self.flag(node, "sync-call",
                      f"{canon.split('.')[-1]} blocks on device→host "
                      "transfer — the steady-state no-sync rule "
                      "(DESIGN.md §11)")
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr in ("block_until_ready", "item")):
            self.flag(node, "sync-call",
                      f".{node.func.attr}() syncs the array to host — the "
                      "steady-state no-sync rule (DESIGN.md §11)")
        elif ((canon == "float"
               or canon in ("numpy.asarray", "numpy.array"))
              and node.args
              and self._contains_device_call(node.args[0])):
            self.flag(node, "sync-call",
                      f"{canon}(…) over a fresh jax result forces a "
                      "device→host sync — the steady-state no-sync rule "
                      "(DESIGN.md §11)")

        # engine-outside-core: construction + legacy compile entry points
        if not self.in_core:
            if self._is_engine_name(node.func):
                self.flag(node, "engine-outside-core",
                          "Engine(...) constructed outside core/ — the "
                          "session facade (repro.connect → Database) is "
                          "the public compile surface (DESIGN.md §9)")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in ("compile", "compile_incremental",
                                         "_compile", "_compile_incremental")
                  and (node.func.attr.endswith("compile_incremental")
                       or (isinstance(node.func.value, ast.Name)
                           and node.func.value.id in self.engine_vars))):
                self.flag(node, "engine-outside-core",
                          f".{node.func.attr}(...) on an Engine outside "
                          "core/ — use Database.views(queries"
                          + (", maintain=True" if "incremental"
                             in node.func.attr else "") + ")")

        # random-key: literal PRNGKey seeds
        if canon.endswith("random.PRNGKey"):
            if node.args and isinstance(node.args[0], ast.Constant):
                self.flag(node, "random-key",
                          "PRNGKey with a literal seed — thread keys/seeds "
                          "from config so parallel runs don't share "
                          "randomness")

        self.generic_visit(node)


def lint_source(source: str, rel: str) -> List[Violation]:
    """Lint one module's source text (repo-relative path for reporting)."""
    tree = ast.parse(source, filename=rel)
    linter = _Linter(rel)
    linter.visit(tree)
    return linter.violations


def load_allowlist(path) -> Dict[str, Dict[str, str]]:
    """``{rule: {repo-relative-posix-path: reason}}``; validates shape so a
    malformed allowlist fails loudly instead of silently allowing."""
    with open(path) as f:
        data = json.load(f)
    for rule, entries in data.items():
        if rule not in RULES:
            raise ValueError(f"allowlist names unknown rule {rule!r} "
                             f"(rules: {', '.join(RULES)})")
        for p, reason in entries.items():
            if not isinstance(reason, str) or not reason.strip():
                raise ValueError(f"allowlist entry {rule}/{p} needs a "
                                 "non-empty reason string")
    return data


def lint_paths(paths: Sequence, allowlist: Dict[str, Dict[str, str]],
               root) -> List[Violation]:
    """Lint every ``.py`` file under the given paths; returns the
    violations that survive the allowlist, sorted for stable output."""
    root = Path(root)
    files: List[Path] = []
    for p in paths:
        p = Path(p)
        files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    out: List[Violation] = []
    for f in files:
        try:
            rel = f.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = f.as_posix()
        for v in lint_source(f.read_text(), rel):
            if v.path not in allowlist.get(v.rule, {}):
                out.append(v)
    return sorted(out, key=lambda v: (v.path, v.line, v.col, v.rule))


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Lint engine contracts (DESIGN.md §12) over src/")
    ap.add_argument("paths", nargs="*", default=["src"],
                    help="files or directories to lint (default: src)")
    ap.add_argument("--allowlist", default="tools/lint_allowlist.json",
                    help="committed allowlist JSON")
    ap.add_argument("--root", default=".",
                    help="repo root for relative paths (default: cwd)")
    args = ap.parse_args(argv)
    allowlist = (load_allowlist(args.allowlist)
                 if Path(args.allowlist).exists() else {})
    violations = lint_paths(args.paths, allowlist, args.root)
    for v in violations:
        print(v.render())
    if violations:
        print(f"\n{len(violations)} contract violation(s)", file=sys.stderr)
        return 1
    print("engine contracts clean "
          f"({', '.join(RULES)}; allowlist entries: "
          f"{sum(len(v) for v in allowlist.values())})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
