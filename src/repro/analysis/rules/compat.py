"""SL001 compat-policy: version-sensitive JAX/Pallas names stay in jax_compat.

ROADMAP standing policy: every API surface that was renamed across JAX
releases (Pallas TPU memory spaces, compiler params, ``dimension_semantics``,
``make_mesh`` axis types, ``shard_map``) is used through the names in
``repro/utils/jax_compat.py`` -- never directly.  A direct use works today
and breaks in every file at the next rename, instead of in one module.

The banned-name table is **read out of jax_compat's module docstring** (the
RST table that already documents each shim row): every ``pltpu.X`` /
``jax.x.y`` / ``kwarg=`` token between the table rules is banned outside the
compat module itself.  Adding a shim row to the docstring therefore *is*
extending the lint -- one source of truth.  When the sweep does not include
jax_compat.py (fixture runs), a frozen fallback copy of the table is used.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Set, Tuple

from repro.analysis.astutil import dotted, iter_functions, parent_map
from repro.analysis.engine import Finding, Project, register

RULE = "SL001"
COMPAT_SUFFIX = "utils/jax_compat.py"

#: modules whose import aliases are tracked for banned-attribute checks
PLTPU_MODULE = "jax.experimental.pallas.tpu"

# Frozen copy of the jax_compat docstring table tokens, used only when the
# compat module itself is outside the sweep (unit-test fixtures).  Keep in
# sync with the docstring; the repo sweep always prefers the live docstring.
FALLBACK_TOKENS = (
    "pltpu.MemorySpace",
    "pltpu.CompilerParams",
    "dimension_semantics=", "GridDimensionSemantics",
    "pltpu.VMEM",
    "axis_types=",
    "jax.make_mesh",
    "jax.experimental.shard_map", "jax.shard_map",
    "check_vma=",
    "jax.profiler.TraceAnnotation",
)

_TOKEN_RE = re.compile(r"``([^`]+)``")
_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_.]*$")


def _docstring_tokens(project: Project) -> Tuple[str, ...]:
    sf = project.find_file(COMPAT_SUFFIX)
    if sf is None:
        return FALLBACK_TOKENS
    doc = ast.get_docstring(sf.tree) or ""
    # restrict to the RST table region (between the first and last ==== rule)
    rules = [m.start() for m in re.finditer(r"^=+\s+=+", doc, re.M)]
    region = doc[rules[0]: rules[-1]] if len(rules) >= 2 else doc
    tokens = []
    for tok in _TOKEN_RE.findall(region):
        tok = tok.strip()
        if tok.endswith("="):
            tokens.append(tok)
        elif _NAME_RE.match(tok):
            tokens.append(tok)
    return tuple(tokens) or FALLBACK_TOKENS


def _classify(tokens: Iterable[str]):
    """Split table tokens into banned kwargs / pltpu attrs / dotted paths."""
    kwargs: Set[str] = set()
    pltpu_attrs: Set[str] = set()
    paths: Set[str] = set()
    for tok in tokens:
        if tok.endswith("="):
            kwargs.add(tok[:-1])
        elif tok.startswith("pltpu."):
            pltpu_attrs.add(tok.split(".", 1)[1])
        elif "." in tok:
            paths.add(tok)
        else:  # bare class-like name (e.g. GridDimensionSemantics)
            pltpu_attrs.add(tok)
    return kwargs, pltpu_attrs, paths


def _pltpu_aliases(tree: ast.AST) -> Set[str]:
    """Local names bound to the Pallas TPU module by imports."""
    aliases: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for a in node.names:
                if f"{mod}.{a.name}" == PLTPU_MODULE:
                    aliases.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == PLTPU_MODULE:
                    aliases.add(a.asname or a.name.split(".")[0])
    return aliases


def _context_of(node, ctx_ranges) -> str:
    for qual, lo, hi in ctx_ranges:
        if lo <= node.lineno <= hi:
            return qual
    return ""


@register(
    RULE, "compat-policy",
    "Version-sensitive JAX/Pallas names must route through "
    "repro/utils/jax_compat.py (its docstring table is the banned list).",
)
def check(project: Project) -> Iterable[Finding]:
    kwargs, pltpu_attrs, paths = _classify(_docstring_tokens(project))
    findings: List[Finding] = []
    for rel, sf in sorted(project.files.items()):
        if rel.endswith(COMPAT_SUFFIX):
            continue
        aliases = _pltpu_aliases(sf.tree)
        parents = parent_map(sf.tree)
        ctx_ranges = [
            (q, n.lineno, max(n.lineno, getattr(n, "end_lineno", n.lineno)))
            for q, n in iter_functions(sf.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]

        def hit(node, message):
            findings.append(Finding(
                rule=RULE, path=rel, line=node.lineno,
                col=node.col_offset, message=message,
                context=_context_of(node, ctx_ranges)))

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                for a in node.names:
                    full = f"{mod}.{a.name}"
                    if full in paths or mod in paths:
                        hit(node, f"direct import of `{full}`: use the "
                                  f"shim in repro/utils/jax_compat.py")
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name in paths:
                        hit(node, f"direct import of `{a.name}`: use the "
                                  f"shim in repro/utils/jax_compat.py")
            elif isinstance(node, ast.Attribute):
                # only outermost chains: `a.b.c` reports once, not per link
                par = parents.get(node)
                if isinstance(par, ast.Attribute) and par.value is node:
                    continue
                path = dotted(node)
                if path is None:
                    continue
                parts = path.split(".")
                if (len(parts) >= 2 and parts[0] in aliases
                        and parts[1] in pltpu_attrs):
                    hit(node, f"direct use of `pltpu.{parts[1]}`: import the "
                              f"shimmed name from repro/utils/jax_compat.py")
                elif path in paths or any(
                        path.startswith(p + ".") for p in paths):
                    hit(node, f"direct use of `{path}`: use the wrapper in "
                              f"repro/utils/jax_compat.py")
            elif isinstance(node, ast.Call):
                callee = dotted(node.func) or ""
                for kw in node.keywords:
                    if kw.arg in kwargs:
                        hit(kw.value,
                            f"version-sensitive kwarg `{kw.arg}=` passed to "
                            f"`{callee or '<call>'}`: use the compat helper "
                            f"in repro/utils/jax_compat.py")
    return findings
