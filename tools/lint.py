#!/usr/bin/env python
"""Static-check gate (the reference's astyle + cppcheck station,
tools/astyle/run.sh + tools/cppcheck/run.sh - README.md:116-129).

No third-party linters exist in this environment, so this is a small
stdlib checker tuned to the rules the tree actually follows:

Python (ast-based, so no false positives from strings/comments):
  - parses (syntax gate)
  - no unused imports (``from __future__ import annotations`` and
    ``__init__.py`` re-exports are exempt; a ``# noqa`` on the import
    line opts out)
  - no bare ``except:``
  - no mutable default arguments
  - no tabs, no trailing whitespace, lines <= 96 chars
  - no raw ``os.environ`` READS of ``HCLIB_TPU_*`` names outside
    ``runtime/env.py`` (the typed registry is the single parse point;
    writes - tests seeding the environment - stay legal)
  - every ``HCLIB_TPU_*`` name mentioned anywhere in the tree must have
    a row in the ``runtime/env.py`` registry (the doc table cannot
    silently lag the code)
  - every ``TR_*``/``SC_*``/``CR_*``/``FLT_*``/``FS_*`` tag or
    payload-code constant defined in ``device/tracebuf.py`` must have
    a name row in its family's decode table (``TAG_NAMES`` /
    ``SC_NAMES`` / ``CR_NAMES`` / ``FLT_NAMES`` / ``FS_NAMES`` - what
    the metrics summarizer and the Perfetto exporter label with) AND a
    decode mention in ``tools/timeline.py`` - the one-table-edit
    invariant the TR_SCALE/SC_* plumbing relies on, enforced instead
    of remembered (both files parsed as ASTs, stdlib-only)

C++ (native/src):
  - no tabs, no trailing whitespace, lines <= 100 chars

Usage: ``python tools/lint.py [paths...]`` (default: the whole repo).
Exit 1 on any violation; the violations print as ``path:line: message``.
CI runs this before the test suite; tests/test_native.py runs it too so
a plain ``pytest`` catches violations locally.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Iterator, List, Optional, Set, Tuple

PY_MAX_LINE = 96
CC_MAX_LINE = 100
# The env-registry module: the ONLY file allowed to read HCLIB_TPU_*
# names from os.environ, and the source of truth for the name table.
ENV_MODULE = os.path.join("hclib_tpu", "runtime", "env.py")
_ENV_NAME = re.compile(r"HCLIB_TPU_[A-Z][A-Z0-9_]*")
SKIP_DIRS = {
    ".git", ".jax_cache", "__pycache__", ".pytest_cache", ".hypothesis",
    "perf-logs", ".claude", "build", "dist", ".eggs",
    # git-ignored: builders' chip scripts, their unpacked copies of the
    # parent and of the staged tree, traces, and what chiprun brings back
    ".bench_scratch", ".bench_parent", ".bench_tree", ".bench_trace",
    "chiprun_out",
}


def _files(paths: List[str]) -> Iterator[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(d for d in dirs if d not in SKIP_DIRS)
            for f in sorted(files):
                if f.endswith((".py", ".cpp", ".cc", ".hpp", ".h")):
                    yield os.path.join(root, f)


def _check_whitespace(
    path: str, src: str, max_line: int
) -> List[Tuple[int, str]]:
    out = []
    for i, line in enumerate(src.splitlines(), 1):
        if "\t" in line:
            out.append((i, "tab character"))
        if line != line.rstrip():
            out.append((i, "trailing whitespace"))
        if len(line) > max_line:
            out.append((i, f"line too long ({len(line)} > {max_line})"))
    return out


def _used_names(tree: ast.AST) -> set:
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            # attribute roots resolve through Name nodes already; nothing
            # extra needed, but keep the branch for clarity
            pass
    return used


def _is_os_environ(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "environ"
        and isinstance(node.value, ast.Name)
        and node.value.id == "os"
    )


def _hclib_names(node: ast.AST) -> Set[str]:
    """HCLIB_TPU_* tokens inside any string constants under ``node``."""
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out |= set(_ENV_NAME.findall(n.value))
    return out


def registry_names(repo: str) -> Set[str]:
    """Registered names (canonical + legacy aliases) parsed from the
    env module's AST - no import, so the linter stays stdlib-only and
    works on a tree that doesn't import."""
    with open(os.path.join(repo, ENV_MODULE)) as f:
        tree = ast.parse(f.read())
    names: Set[str] = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "_v"
        ):
            for arg in [node.args[0]] + [
                kw.value for kw in node.keywords if kw.arg == "legacy"
            ] + (list(node.args[4:5])):
                for n in ast.walk(arg):
                    if isinstance(n, ast.Constant) and isinstance(
                        n.value, str
                    ):
                        names.add(n.value)
    return names


def _check_env_usage(
    path: str, tree: ast.AST, repo: str, registered: Set[str],
    noqa,
) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    rel = os.path.relpath(path, repo)
    is_env_module = rel == ENV_MODULE
    for node in ast.walk(tree):
        # Rule 1: raw environ READS of HCLIB_TPU_* outside the registry.
        hit: Optional[ast.AST] = None
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            # pop is the cleanup-write spelling tests use next to their
            # seeding writes - only the read idioms are flagged.
            and node.func.attr in ("get", "setdefault")
            and _is_os_environ(node.func.value)
            and any(_hclib_names(a) for a in node.args)
        ):
            hit = node
        elif (
            isinstance(node, ast.Subscript)
            and _is_os_environ(node.value)
            and isinstance(node.ctx, ast.Load)
            and _hclib_names(node.slice)
        ):
            hit = node
        if hit is not None and not is_env_module and not noqa(hit.lineno):
            out.append((
                hit.lineno,
                "raw os.environ read of an HCLIB_TPU_* name: go "
                "through hclib_tpu.runtime.env (typed registry)",
            ))
    # Rule 2: every mentioned name has a registry row.
    for name in sorted(_hclib_names(tree) - registered):
        out.append((
            1,
            f"env var {name} is not in the runtime/env.py registry: "
            "add a row (name, type, default, doc)",
        ))
    return out


TRACEBUF = os.path.join("hclib_tpu", "device", "tracebuf.py")
TIMELINE = os.path.join("tools", "timeline.py")
# Structural constants sharing the tag prefixes but not record tags.
_TAG_EXEMPT = {"TR_WORDS"}
# Tag/code families and the name table each must key into (TR_* record
# tags; SC_* scale kinds; CR_* credit deltas; FLT_* fault codes; CK_*
# checkpoint-store subcodes; FS_* reserved for fault-stats words if
# they ever move tracebuf-side).
_TAG_TABLES = {
    "TR_": "TAG_NAMES",
    "SC_": "SC_NAMES",
    "CR_": "CR_NAMES",
    "FLT_": "FLT_NAMES",
    "CK_": "CK_NAMES",
    "FS_": "FS_NAMES",
}
_TAG_RE = re.compile(r"^(TR|SC|CR|FLT|CK|FS)_[A-Z][A-Z0-9_]*$")


def check_trace_tables(repo: str) -> List[Tuple[str, int, str]]:
    """The trace-tag coverage rule: every TR_*/SC_*/CR_*/FLT_*/FS_*
    constant assigned at tracebuf.py module level (by literal OR
    expression - ``TR_NEW = TR_OLD + 1`` counts) must (a) be a key of
    its family's name table (``_TAG_TABLES``) - the single table
    metrics and Perfetto label from - and (b) be mentioned by
    tools/timeline.py (its decode rows reference record tags as
    ``tb.<TAG>``; payload-code families decode through their name
    table, so the table reference counts). Violations: (path, line,
    message)."""
    with open(os.path.join(repo, TRACEBUF)) as f:
        tree = ast.parse(f.read())
    tags: List[Tuple[str, int]] = []
    tables: dict = {}
    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if not isinstance(t, ast.Name):
                    continue
                if (
                    _TAG_RE.match(t.id)
                    and t.id not in _TAG_EXEMPT
                    and not t.id.endswith("_NAMES")
                    # Any value expression counts (TR_NEW = TR_OLD + 1
                    # is the natural way to append a tag); only dict/
                    # sequence containers are structural, not tags.
                    and not isinstance(
                        node.value,
                        (ast.Dict, ast.List, ast.Tuple, ast.Set),
                    )
                ):
                    tags.append((t.id, node.lineno))
                if t.id in set(_TAG_TABLES.values()):
                    keys = set()
                    for n in ast.walk(node.value):
                        if isinstance(n, ast.Name):
                            keys.add(n.id)
                    tables[t.id] = keys
    with open(os.path.join(repo, TIMELINE)) as f:
        tl_tree = ast.parse(f.read())
    tl_names: Set[str] = set()
    for n in ast.walk(tl_tree):
        if isinstance(n, ast.Attribute):
            tl_names.add(n.attr)
        elif isinstance(n, ast.Name):
            tl_names.add(n.id)
    out: List[Tuple[str, int, str]] = []
    for tag, lineno in tags:
        table = next(
            t for p, t in _TAG_TABLES.items() if tag.startswith(p)
        )
        named = tag in tables.get(table, set())
        if not named:
            out.append((
                TRACEBUF, lineno,
                f"trace tag {tag} has no {table} row (the metrics/"
                "Perfetto name tables must cover every tag - one table "
                "edit, not three drifting copies)",
            ))
        # TR_* tags decode individually; SC_*/FS_* decode through their
        # name table, so the table being consulted by timeline.py
        # satisfies the decode-row half for them.
        needed = tag if tag.startswith("TR_") else table
        if needed not in tl_names:
            out.append((
                TRACEBUF, lineno,
                f"trace tag {tag} has no decode row in tools/"
                f"timeline.py ({needed} never referenced): add a "
                "branch (or name-table rendering) so the tag is "
                "legible in Perfetto",
            ))
    return out


def _check_python(
    path: str, src: str, repo: Optional[str] = None,
    registered: Optional[Set[str]] = None,
) -> List[Tuple[int, str]]:
    out = _check_whitespace(path, src, PY_MAX_LINE)
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        out.append((e.lineno or 0, f"syntax error: {e.msg}"))
        return out
    lines = src.splitlines()

    def noqa(lineno: int) -> bool:
        return 0 < lineno <= len(lines) and "# noqa" in lines[lineno - 1]

    if repo is not None and registered is not None:
        out.extend(_check_env_usage(path, tree, repo, registered, noqa))

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            out.append((node.lineno, "bare except:"))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None
            ]:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    out.append(
                        (node.lineno,
                         f"mutable default argument in {node.name}()")
                    )
    if os.path.basename(path) != "__init__.py":
        used = _used_names(tree)
        # Names referenced only inside docstring doctests or __all__
        # strings count as used (modules re-export through __all__).
        exported = set()
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets
                )
                and isinstance(node.value, (ast.List, ast.Tuple))
            ):
                exported |= {
                    e.value
                    for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                }
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = (a.asname or a.name).split(".")[0]
                    if (
                        name not in used
                        and name not in exported
                        and not noqa(node.lineno)
                    ):
                        out.append((node.lineno, f"unused import '{name}'"))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    name = a.asname or a.name
                    if (
                        name not in used
                        and name not in exported
                        and not noqa(node.lineno)
                    ):
                        out.append((node.lineno, f"unused import '{name}'"))
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = argv or [repo]
    try:
        registered = registry_names(repo)
    except OSError:
        registered = None  # env module missing: skip the env rules
    except SyntaxError:
        # env.py's own syntax error surfaces as a normal finding in the
        # per-file loop below; don't die with a traceback here.
        registered = None
    bad = 0
    for path in _files(paths):
        with open(path, errors="replace") as f:
            src = f.read()
        if path.endswith(".py"):
            problems = _check_python(
                path, src, repo if registered is not None else None,
                registered,
            )
        else:
            problems = _check_whitespace(path, src, CC_MAX_LINE)
        for lineno, msg in sorted(problems):
            print(f"{os.path.relpath(path, repo)}:{lineno}: {msg}")
            bad += 1
    try:
        table_problems = check_trace_tables(repo)
    except (OSError, SyntaxError):
        table_problems = []  # missing/broken file surfaces above
    for rel, lineno, msg in table_problems:
        print(f"{rel}:{lineno}: {msg}")
        bad += 1
    if bad:
        print(f"lint: {bad} violation(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
