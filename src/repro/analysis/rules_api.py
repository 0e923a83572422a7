"""RPR401/RPR402/RPR403/RPR404 — deprecated API surfaces.

The PR 4 API redesign consolidated query configuration into
:class:`repro.core.results.QueryOptions` and split the legacy
``AlignmentIndex`` god-object into ``IndexBuilder`` + ``SearchIndex``.
The old spellings still work through shims that emit
``DeprecationWarning`` — these rules keep new first-party code off them
so the shims can eventually be deleted:

* **RPR401** — legacy per-call query kwargs (``backend=``,
  ``fanout=``, ``sketches=``) on
  ``find``/``find_batch``/``batch_query`` method calls; pass
  ``options=QueryOptions(...)``.
* **RPR402** — any call using ``legacy_tuples=``; consume
  :class:`QueryResult`/:class:`Alignment` objects instead.
* **RPR403** — any mention of ``AlignmentIndex`` outside its shim module
  (``src/repro/core/index.py``); use ``IndexBuilder`` (mutable) or
  ``SearchIndex`` (frozen).
* **RPR404** — per-stage backend kwargs (``sketch_backend=``,
  ``sketches=``) on *any* call to
  ``query``/``batch_query``/``find``/``find_batch``, bare functions
  included.  The PR 10 execution-plan redesign folded these into
  ``QueryOptions``; pass ``options=QueryOptions(plan=..., ...)``.
  RPR401 predates the plan API and only sees method receivers — RPR404
  closes the gap for the core ``batch_query(...)`` function (the spelling
  benchmarks use), so on method calls it reports only the kwargs RPR401
  does not already cover.

Deprecation *tests* exercise these surfaces on purpose — they carry
line-scoped ``# repro: allow[...]`` waivers.
"""

from __future__ import annotations

import ast

from .framework import Finding, Project, checker

RPR401 = ("RPR401",
          "legacy query kwarg on find/find_batch/batch_query; use "
          "options=QueryOptions(...)")
RPR402 = ("RPR402",
          "legacy_tuples= is deprecated; consume QueryResult/Alignment "
          "objects")
RPR403 = ("RPR403",
          "AlignmentIndex is deprecated outside its shim; use "
          "IndexBuilder/SearchIndex")
RPR404 = ("RPR404",
          "per-stage backend kwarg on a query call; use "
          "options=QueryOptions(plan=..., ...)")

SHIM_FILE = "src/repro/core/index.py"

_QUERY_METHODS = frozenset({"find", "find_batch", "batch_query"})
_LEGACY_KWARGS = frozenset({"backend", "fanout", "sketches"})
_QUERY_CALLS = frozenset({"query", "batch_query", "find", "find_batch"})
_STAGE_KWARGS = frozenset({"sketch_backend", "sketches"})


@checker(RPR401, RPR402, RPR403, RPR404)
def check_api_deprecations(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for sf in project.files:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                kwargs = {kw.arg for kw in node.keywords if kw.arg}
                is_method = isinstance(node.func, ast.Attribute)
                callee = (node.func.attr if is_method else
                          node.func.id if isinstance(node.func, ast.Name)
                          else None)
                # method calls only: the core `query`/`batch_query`
                # *functions* take these as real parameters
                if is_method and callee in _QUERY_METHODS:
                    legacy = sorted(kwargs & _LEGACY_KWARGS)
                    if legacy:
                        findings.append(Finding(
                            rule="RPR401", path=sf.rel, line=node.lineno,
                            message=f".{callee}(..., "
                                    f"{'=, '.join(legacy)}=) uses legacy "
                                    "query kwargs; pass options="
                                    "QueryOptions(...)"))
                if callee in _QUERY_CALLS:
                    stage = kwargs & _STAGE_KWARGS
                    if is_method:
                        # RPR401 already reports these on methods
                        stage -= _LEGACY_KWARGS
                    if stage:
                        shown = sorted(stage)
                        findings.append(Finding(
                            rule="RPR404", path=sf.rel, line=node.lineno,
                            message=f"{callee}(..., {'=, '.join(shown)}=) "
                                    "passes deprecated per-stage kwargs; "
                                    "pass options=QueryOptions(plan=..., "
                                    "...)"))
                if "legacy_tuples" in kwargs:
                    findings.append(Finding(
                        rule="RPR402", path=sf.rel, line=node.lineno,
                        message="legacy_tuples= requests deprecated "
                                "tuple results; consume QueryResult/"
                                "Alignment objects"))
            if sf.rel != SHIM_FILE:
                findings.extend(_alignment_index_use(sf, node))
    return findings


def _alignment_index_use(sf, node: ast.AST) -> list[Finding]:
    hit = None
    if isinstance(node, ast.Name) and node.id == "AlignmentIndex":
        hit = node.lineno
    elif isinstance(node, ast.Attribute) and node.attr == "AlignmentIndex":
        hit = node.lineno
    elif isinstance(node, ast.ImportFrom) and any(
            a.name == "AlignmentIndex" for a in node.names):
        hit = node.lineno
    if hit is None:
        return []
    return [Finding(
        rule="RPR403", path=sf.rel, line=hit,
        message="AlignmentIndex is a deprecated shim; build with "
                "IndexBuilder and freeze() to SearchIndex")]
