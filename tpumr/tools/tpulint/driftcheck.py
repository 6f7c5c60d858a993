"""Rule family 4: drift between prose and code.

Operators navigate this system through ``docs/OPERATIONS.md`` — metric
names to graph, fault-injection seams to pull in chaos drills. A
renamed metric or seam that the doc still advertises is a page that
lies during an incident. The reference tree's equivalent failure mode
was `/** MODIFIED FOR GPGPU Usage! **/` comment tags drifting away
from the code they annotated (PAPER.md).

``drift-metric``
    A backticked code-ish token in OPERATIONS.md (``tpumr_*`` series,
    ``*_seconds{...}`` histograms, counters, identifiers) that nothing
    in ``tpumr/`` registers or defines. Matching is prefix-aware:
    ``tpumr_`` is the Prometheus namespace the exporter prepends, and
    composite gauges flatten to ``name_key``.

``drift-fi``
    A fault-seam name advertised in OPERATIONS.md or the
    ``tpumr/utils/fi.py`` module docstring (``tpumr.fi.<point>...``)
    that no ``maybe_fail()``/``fires()`` call site can ever fire.
    Placeholder syntax is honored: ``tpu.execute[.d<id>]`` means the
    base seam plus a templated variant.
"""

from __future__ import annotations

import ast
import os
import re

from tpumr.tools.tpulint.core import (Finding, Module, call_name,
                                      const_str, joined_prefix)

_BACKTICK = re.compile(r"`([^`\n]+)`")
_TOKEN = re.compile(r"^[a-z][a-z0-9_]*$")
_METRIC_CALLS = {"incr", "set_gauge", "histogram", "Histogram"}
_FI_CALLS = {"maybe_fail", "fires", "fired"}
_SEAM = re.compile(r"^[a-z][a-z0-9_<>]*(\.[a-z0-9_<>]+)+$")


def _registered_metrics(mods: "list[Module]") -> set[str]:
    names: set[str] = set()
    for m in mods:
        consts: dict[str, str] = {}
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        consts[tgt.id] = node.value.value
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Call) and \
                    call_name(node) in _METRIC_CALLS and node.args:
                arg = node.args[0]
                name = const_str(arg)
                if name is None and isinstance(arg, ast.Name):
                    name = consts.get(arg.id)
                if name is None and isinstance(arg, ast.JoinedStr):
                    name = joined_prefix(arg) + "*"
                if name is None and isinstance(arg, ast.BinOp) and \
                        isinstance(arg.op, ast.Add):
                    # reg.histogram(name + "_request_bytes"): dynamic
                    # prefix, literal suffix
                    suffix = const_str(arg.right)
                    if suffix:
                        name = "*" + suffix
                if name:
                    names.add(name)
                    # internal labeled-series convention is
                    # "family|label=value" — docs write {label=...};
                    # the family name is the identity
                    names.add(name.split("|", 1)[0])
    return names


def _identifiers(mods: "list[Module]") -> set[str]:
    ids: set[str] = set()
    for m in mods:
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Name):
                ids.add(node.id)
            elif isinstance(node, ast.Attribute):
                ids.add(node.attr)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                ids.add(node.name)
            elif isinstance(node, ast.arg):
                ids.add(node.arg)
            elif isinstance(node, ast.keyword) and node.arg:
                ids.add(node.arg)
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    _TOKEN.match(node.value):
                # dict-key / counter-name string literals count: docs
                # legitimately name JSON fields and counter rows
                ids.add(node.value)
        ids.update(k.split("=")[0] for k in ())
    return ids


def _metric_known(token: str, metrics: set[str]) -> bool:
    base = token.split("{", 1)[0]
    for cand in ({base} | ({base[len("tpumr_"):]}
                           if base.startswith("tpumr_") else set())):
        if cand in metrics:
            return True
        for name in metrics:
            if name.endswith("*") and cand.startswith(name[:-1]):
                return True
            if name.startswith("*") and cand.endswith(name[1:]):
                return True
            # composite gauges flatten to name_key in exposition
            if not name.startswith("*") and \
                    cand.startswith(name.rstrip("*") + "_"):
                return True
    return False


def _root_modules(root: str) -> "list[Module]":
    """Top-level repo scripts (chip_smoke.py) — their row keys and
    identifiers are legitimately named in OPERATIONS.md."""
    import glob

    from tpumr.tools.tpulint.core import Pragmas
    out: "list[Module]" = []
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src)
        except (OSError, SyntaxError):
            continue
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        out.append(Module(path=path, rel=rel, source=src, tree=tree,
                          pragmas=Pragmas("")))
    return out


def check_metric_drift(mods: "list[Module]", root: str) \
        -> "list[Finding]":
    doc = os.path.join(root, "docs", "OPERATIONS.md")
    if not os.path.exists(doc):
        return []
    rel = os.path.relpath(doc, root).replace(os.sep, "/")
    corpus = mods + _root_modules(root)
    metrics = _registered_metrics(corpus)
    idents = _identifiers(corpus)
    findings: "list[Finding]" = []
    seen: set[str] = set()
    with open(doc, encoding="utf-8") as f:
        for lineno, line in enumerate(f, start=1):
            if "tpulint: disable=drift-metric" in line:
                continue   # markdown can't carry python pragmas; an
                           # HTML comment on the line suppresses it
            for span in _BACKTICK.findall(line):
                token = span.strip()
                base = token.split("{", 1)[0]
                if "_" not in base or not _TOKEN.match(base):
                    continue
                if token in seen:
                    continue
                if _metric_known(token, metrics) or base in idents:
                    continue
                seen.add(token)
                findings.append(Finding(
                    rule="drift-metric", path=rel, line=lineno,
                    message=(f"docs name `{token}` but nothing in "
                             f"tpumr/ registers or defines it — "
                             f"renamed or removed?")))
    return findings


# ------------------------------------------------------------------- fi


def _fired_points(mods: "list[Module]") -> set[str]:
    """Seam names call sites can fire; f-string seams contribute their
    literal prefix + '*'."""
    points: set[str] = set()
    for m in mods:
        if m.rel.endswith("utils/fi.py"):
            continue
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Call) and \
                    call_name(node) in _FI_CALLS and node.args:
                arg = node.args[0]
                point = const_str(arg)
                if point is None and isinstance(arg, ast.JoinedStr):
                    point = joined_prefix(arg) + "*"
                if point:
                    points.add(point)
    return points


def _expand_placeholder(tok: str) -> "list[str]":
    """'tpu.execute[.d<id>]' -> ['tpu.execute', 'tpu.execute.d*'];
    '<...>' placeholders become '*'."""
    m = re.match(r"^([^\[\]]*)\[([^\[\]]+)\](.*)$", tok)
    if m:
        variants = [m.group(1) + m.group(3),
                    m.group(1) + m.group(2) + m.group(3)]
    else:
        variants = [tok]
    return [re.sub(r"<[^>]*>", "*", v) for v in variants]


def _seam_known(seam: str, fired: set[str]) -> bool:
    """A doc seam matches a fired point exactly, or by wildcard prefix
    overlap in either direction (doc 'tpu.execute.d*' vs fired
    f-string prefix 'tpu.execute.d*')."""
    if seam in fired:
        return True
    want = seam[:-1] if seam.endswith("*") else None
    for p in fired:
        got = p[:-1] if p.endswith("*") else None
        if want is not None and got is not None:
            if got.startswith(want) or want.startswith(got):
                return True
        elif want is not None and p.startswith(want):
            return True
        elif got is not None and seam.startswith(got):
            return True
    return False


def _doc_seams(text: str) -> "list[tuple[str, int]]":
    """Seam names a document advertises: ``tpumr.fi.<seam>.probability``
    / ``.max.failures`` config references, with placeholders."""
    out: "list[tuple[str, int]]" = []
    for lineno, line in enumerate(text.splitlines(), start=1):
        for m in re.finditer(
                r"tpumr\.fi\.([a-z0-9_.<>\[\]]+?)"
                r"\.(?:probability|max\.failures)", line):
            out.append((m.group(1), lineno))
    return out


def _fi_docstring_seams(fi_mod: Module) -> "list[tuple[str, int]]":
    """Bare seam names listed in fi.py's MODULE docstring (the seam
    catalog)."""
    doc = ast.get_docstring(fi_mod.tree, clean=False) or ""
    out: "list[tuple[str, int]]" = []
    for lineno, line in enumerate(doc.splitlines(), start=2):
        for raw in re.split(r"[\s/]+", line):
            tok = raw.strip(",;:()").rstrip(".")
            if not _SEAM.match(tok) or tok.startswith("tpumr."):
                continue
            segs = tok.replace("<", " ").replace(">", " ").split(".")
            if all(len(s.strip()) <= 1 for s in segs):
                continue   # 'e.g', 'i.e'
            out.append((tok, lineno))
    return out


def check_fi_drift(mods: "list[Module]", root: str) -> "list[Finding]":
    fired = _fired_points(mods)
    findings: "list[Finding]" = []
    doc = os.path.join(root, "docs", "OPERATIONS.md")
    sources: "list[tuple[str, list[tuple[str, int]]]]" = []
    if os.path.exists(doc):
        with open(doc, encoding="utf-8") as f:
            sources.append((
                os.path.relpath(doc, root).replace(os.sep, "/"),
                _doc_seams(f.read())))
    fi_mod = next((m for m in mods if m.rel.endswith("utils/fi.py")),
                  None)
    if fi_mod is not None:
        seams = _fi_docstring_seams(fi_mod) + _doc_seams(fi_mod.source)
        sources.append((fi_mod.rel, seams))
    for rel, seams in sources:
        reported: set[str] = set()
        for tok, lineno in seams:
            for seam in _expand_placeholder(tok):
                if seam in reported or _seam_known(seam, fired):
                    continue
                reported.add(seam)
                findings.append(Finding(
                    rule="drift-fi", path=rel, line=lineno,
                    message=(f"fault seam '{seam}' is advertised but no "
                             f"maybe_fail()/fires() call site fires it")))
    return findings
