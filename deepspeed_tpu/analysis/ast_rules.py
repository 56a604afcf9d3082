"""AST rule runner: Python-level lint rules over the library source tree.

One framework for every source-level rule — the bare-``assert`` ban, the
metric-tag schema lint that used to be a private walker inside
``observability/schema.py``, and the hot-path host-sync rule
(:mod:`.host_sync`). Rules are objects with ``name`` and
``check(tree, source_lines, relpath) -> [Finding]``; :func:`run_ast_rules`
walks a file set once, parses each file once, and feeds every rule — so
adding a contract to a future PR is one rule class, not one bespoke walker.

Rule catalog:

- :class:`BareAssertRule` — no bare ``assert`` in library (non-test) code:
  asserts vanish under ``python -O``, so a guard written as one is a guard
  that does not exist in optimized deployments (the exact bug class PR 3
  fixed in ``chunked_matmul_reduce_scatter``). Tests keep their asserts
  (pytest rewrites them); library code raises explicit exceptions.
- :class:`EmissionTagRule` — every metric-tag literal that feeds an emission
  site resolves against the declared schema (``observability.schema.TAGS``).
"""

import ast
import fnmatch
import os
import re
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

from .report import Finding, PassResult, SEVERITY_ERROR


class AstRule:
    """Base: subclasses set ``name`` and implement :meth:`check`."""

    name = "ast-rule"

    def check(self, tree: ast.Module, source_lines: List[str],
              relpath: str) -> List[Finding]:
        raise NotImplementedError


# --------------------------------------------------------------- bare assert
class BareAssertRule(AstRule):
    """Ban ``assert`` statements in library code paths."""

    name = "bare_assert"

    def check(self, tree, source_lines, relpath):
        findings = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Assert):
                findings.append(Finding(
                    self.name, SEVERITY_ERROR, f"{relpath}:{node.lineno}",
                    "bare assert in library code — vanishes under python -O; "
                    "raise an explicit exception instead",
                    {"line": node.lineno}))
        return findings


# ------------------------------------------------------------- emission tags
_EMIT_FUNCS = {"write_events", "record_events", "record", "emit", "_write",
               "counter", "gauge", "histogram"}
_TAG_RE = re.compile(r"^(serving|router|Train|inference|latency|flight"
                     r"|anomaly|host)/[A-Za-z0-9_{}*./]+$")


def _literal_tag(node: ast.AST) -> Optional[str]:
    """Render a Str/JoinedStr AST node to a tag literal (f-string
    interpolations become ``*``); None when it isn't tag-shaped."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        text = node.value
    elif isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("*")
        text = "".join(parts)
    else:
        return None
    return text if _TAG_RE.match(text) else None


def iter_emission_tags_from_tree(tree: ast.Module
                                 ) -> Iterator[Tuple[str, int]]:
    """Yield ``(tag_literal, lineno)`` for every tag-shaped string constant
    inside a function that calls one of the emit surfaces (``write_events`` /
    ``record_events`` / registry ``record`` / ``counter``/``gauge``/
    ``histogram``). Docstrings are skipped; constants inside an f-string are
    fragments of the rendered pattern, never tags themselves."""

    def calls_emit(fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                fname = None
                if isinstance(node.func, ast.Attribute):
                    fname = node.func.attr
                elif isinstance(node.func, ast.Name):
                    fname = node.func.id
                if fname in _EMIT_FUNCS:
                    return True
        return False

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not calls_emit(fn):
            continue
        body = fn.body
        # skip the docstring: prose mentions of tags are not emission sites
        if (body and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            body = body[1:]
        for stmt in body:
            fragment_ids = set()
            for node in ast.walk(stmt):
                if isinstance(node, ast.JoinedStr):
                    for sub in ast.walk(node):
                        if sub is not node:
                            fragment_ids.add(id(sub))
            for node in ast.walk(stmt):
                if id(node) in fragment_ids:
                    continue
                tag = _literal_tag(node)
                if tag is not None:
                    yield tag, node.lineno


_SPAN_FUNCS = {"span", "phase", "begin", "start_span", "record_span",
               "record_pause", "instant"}


def iter_span_names_from_tree(tree: ast.Module) -> Iterator[Tuple[str, int]]:
    """Yield ``(name, lineno)`` for the literal first argument of every
    tracer call that names a span (``.span`` / ``.phase`` / ``.begin`` /
    ``.start_span`` / ``.record_span`` / ``.record_pause`` / ``.instant``)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SPAN_FUNCS and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            yield node.args[0].value, node.lineno


def iter_scope_names_from_tree(tree: ast.Module) -> Iterator[Tuple[str, int]]:
    """Yield ``(name, lineno)`` for the literal first argument of every
    ``scope(...)`` call (``observability.scope``, as a ``with`` or as a
    decorator); a first argument that is no literal yields ``None`` for the
    name: the lint cannot hold it to the declared list."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        if (fn.id if isinstance(fn, ast.Name) else
                fn.attr if isinstance(fn, ast.Attribute) else None) != "scope":
            continue
        arg = node.args[0]
        literal = isinstance(arg, ast.Constant) and isinstance(arg.value, str)
        yield (arg.value if literal else None), node.lineno


def iter_emission_tags(path: str) -> Iterator[Tuple[str, int]]:
    """File-path face of :func:`iter_emission_tags_from_tree` (the API
    ``observability.schema`` re-exports)."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    yield from iter_emission_tags_from_tree(tree)


class EmissionTagRule(AstRule):
    """Every emitted metric tag resolves against the declared schema.

    ``resolve`` is injected (``observability.schema.resolve``) so this module
    stays import-cycle-free; ``modules`` restricts the rule to the declared
    emitter files (tag-shaped strings elsewhere — docs, tests — are not
    emission sites). With ``resolve_span`` the same rule holds every span
    name at a tracer call site in ``span_modules`` to the declared span
    table (``observability.schema.SPANS``), and with ``resolve_scope`` every
    ``scope(...)`` call in ``scope_modules`` to the declared device scopes
    (``observability.schema.SCOPES``)."""

    name = "emission_tags"

    def __init__(self, resolve: Callable[[str], Optional[str]],
                 modules: Sequence[str],
                 resolve_span: Optional[Callable[[str], Optional[str]]] = None,
                 span_modules: Sequence[str] = (),
                 resolve_scope: Optional[Callable[[str], Optional[str]]] = None,
                 scope_modules: Sequence[str] = ()):
        self.resolve = resolve
        self.modules = tuple(modules)
        self.resolve_span = resolve_span
        self.span_modules = tuple(span_modules)
        self.resolve_scope = resolve_scope
        self.scope_modules = tuple(scope_modules)

    def check(self, tree, source_lines, relpath):
        findings = []
        if relpath in self.modules:
            for tag, lineno in iter_emission_tags_from_tree(tree):
                if self.resolve(tag) is None:
                    findings.append(Finding(
                        self.name, SEVERITY_ERROR, f"{relpath}:{lineno}",
                        f"metric tag {tag!r} is not declared in "
                        "observability.schema.TAGS — declare it (kind + help) "
                        "before emitting it", {"tag": tag}))
        if self.resolve_span is not None and relpath in self.span_modules:
            for name, lineno in iter_span_names_from_tree(tree):
                if self.resolve_span(name) is None:
                    findings.append(Finding(
                        self.name, SEVERITY_ERROR, f"{relpath}:{lineno}",
                        f"span name {name!r} is not declared in "
                        "observability.schema.SPANS — declare it (layer, "
                        "attributes, what reads it) before opening it",
                        {"tag": name}))
        if self.resolve_scope is not None and relpath in self.scope_modules:
            for name, lineno in iter_scope_names_from_tree(tree):
                if name is None or self.resolve_scope(name) is None:
                    findings.append(Finding(
                        self.name, SEVERITY_ERROR, f"{relpath}:{lineno}",
                        f"device scope {name!r} is not a literal declared in "
                        "observability.schema.SCOPES — declare it (layer, "
                        "what it holds, what reads it) before opening it",
                        {"tag": str(name)}))
        return findings


# -------------------------------------------------------------------- runner
#: paths never linted (generated/vendored would go here)
DEFAULT_EXCLUDES = ("tests/*", "*/tests/*")


def library_files(repo_root: str, package: str = "deepspeed_tpu",
                  excludes: Sequence[str] = DEFAULT_EXCLUDES) -> List[str]:
    """Repo-relative paths of every library ``.py`` file under ``package``."""
    out = []
    base = os.path.join(repo_root, package)
    for dirpath, _, names in os.walk(base):
        for name in sorted(names):
            if not name.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, name), repo_root)
            rel = rel.replace(os.sep, "/")
            if any(fnmatch.fnmatch(rel, pat) for pat in excludes):
                continue
            out.append(rel)
    return sorted(out)


def run_ast_rules(repo_root: str, rules: Sequence[AstRule],
                  paths: Optional[Sequence[str]] = None) -> PassResult:
    """Parse each file once; feed every rule. ``paths`` (repo-relative)
    restricts the sweep — the ``--changed-only`` fast mode."""
    if paths is None:
        paths = library_files(repo_root)
    names = "+".join(r.name for r in rules) or "none"
    result = PassResult("ast_rules", names, checked=0)
    for rel in paths:
        full = os.path.join(repo_root, rel)
        if not os.path.exists(full) or not rel.endswith(".py"):
            continue
        with open(full) as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=full)
        except SyntaxError as e:
            result.findings.append(Finding(
                "ast_rules", SEVERITY_ERROR, f"{rel}:{e.lineno or 0}",
                f"syntax error during lint parse: {e.msg}"))
            continue
        result.checked += 1
        lines = source.splitlines()
        for rule in rules:
            result.findings.extend(rule.check(tree, lines, rel))
    return result
