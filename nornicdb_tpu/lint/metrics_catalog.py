"""Metric-catalog drift lint: every import-time metric family must be
documented (the scripts/check_metrics_catalog.py logic, folded into the
nornic-lint framework as its fifth pass — ISSUE 14).

``docs/observability.md`` is the operator-facing catalog of the
``nornicdb_*`` metric families plus the serving-truth vocabularies
(dispatch kinds, canonical tiers, normalized degrade reasons, event
kinds). This module imports every module that registers families at
import time, then reports drift. The standalone CLI
(``scripts/check_metrics_catalog.py``) is a thin shim over this module
— its verdict shape and the ``tests/test_load_truth.py`` entry points
are unchanged.

Unlike the AST passes this one imports the serving stack at *run*
time; it is the reason ``nornic_lint`` reports runtime drift a pure
parse cannot see (a family registered under a computed name still
lands in the process registry).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
from typing import List

# modules that register metric families at import time (module-level
# REGISTRY.counter/histogram/gauge calls). Keep in sync by grepping:
#   grep -rn "REGISTRY\.\(counter\|histogram\|gauge\)(" nornicdb_tpu
IMPORT_TIME_MODULES = (
    "nornicdb_tpu.obs",            # dispatch, stages, cost families
    "nornicdb_tpu.obs.events",     # incident-timeline counter (ISSUE 13)
    "nornicdb_tpu.obs.fleet",      # fleet-aggregator sources gauge
    "nornicdb_tpu.admission",      # shed/deadline/lane families (ISSUE 15)
    "nornicdb_tpu.search.microbatch",
    "nornicdb_tpu.search.broker",  # wire-plane broker families (ISSUE 11)
    "nornicdb_tpu.search.service",
    "nornicdb_tpu.search.cagra",
    "nornicdb_tpu.search.device_bm25",
    "nornicdb_tpu.search.device_quant",
    "nornicdb_tpu.search.tiered_store",  # tiered paging events (ISSUE 17)
    "nornicdb_tpu.search.hybrid_fused",
    "nornicdb_tpu.query.device_graph",
    "nornicdb_tpu.storage.wal",
    "nornicdb_tpu.api.bolt",
    "nornicdb_tpu.api.http_server",
    "nornicdb_tpu.api.qdrant_official_grpc",
    "nornicdb_tpu.api.fleet_router",       # read-fleet router (ISSUE 12)
    "nornicdb_tpu.replication.read_fleet",  # replica lag/failover gauges
    # ISSUE 16: the multi-process fleet modules register no families of
    # their own *today*, but they carry the streaming/posture hot paths
    # — importing them here means any family they grow is caught by
    # this lint the moment it appears, not when the docs drift.
    "nornicdb_tpu.replication.transport",   # dual-plane WAL streaming
    "nornicdb_tpu.replication.fleet_proc",  # subprocess replica fleet
    "nornicdb_tpu.obs.tenant",  # per-tenant attribution (ISSUE 18)
    # ISSUE 19: background device plane — jobs counter + bg_* dispatch
    # kinds registered at import
    "nornicdb_tpu.background.device_plane",
    # ISSUE 20: device-truth calibration plane — compile split,
    # roofline gauges, recompile counter, memory-ledger families
    "nornicdb_tpu.obs.device",
    # ISSUE 26: the hot-path spans' counters — embed worker phases and
    # token fill, the encoder / vector_widen dispatch kinds, the
    # in-memory engine's lock wait; ISSUE 36: the embedder's
    # parameter-bytes gauge
    "nornicdb_tpu.embed.queue",
    "nornicdb_tpu.embed.embedder",
    "nornicdb_tpu.api.qdrant",
    "nornicdb_tpu.storage.memory",
    # ISSUE 32: the brute index's refresh and ship-bytes counters, the
    # id table's `extended`, the `index_update` dispatch kind; ISSUE 34:
    # the `vector_filtered` dispatch kind, and in api.qdrant above the
    # filtered-search counter
    "nornicdb_tpu.search.vector_index",
)

_PREFIX = "nornicdb_"

PASS = "metrics-catalog"


def _expand_braces(text: str) -> str:
    """Expand one level of ``name_{a,b,c}_suffix`` doc shorthand into
    the literal metric names so the substring match sees them."""
    pattern = re.compile(r"(\w*)\{([\w,]+)\}(\w*)")
    out = [text]
    for m in pattern.finditer(text):
        head, alts, tail = m.group(1), m.group(2), m.group(3)
        for alt in alts.split(","):
            out.append(f"{head}{alt}{tail}")
    return "\n".join(out)


def registered_families():
    from nornicdb_tpu.obs import REGISTRY

    for mod in IMPORT_TIME_MODULES:
        importlib.import_module(mod)
    return sorted(f.name for f in REGISTRY.families())


def _documented(expanded: str, name: str) -> bool:
    # word-boundary match: a plain substring test would let e.g. a
    # new nornicdb_stage_seconds family ride inside the documented
    # nornicdb_request_stage_seconds — the exact drift class this
    # lint exists to catch (underscores are word chars, so \b only
    # matches at the full-name edges)
    return re.search(rf"\b{re.escape(name)}\b", expanded) is not None


def missing_from_catalog(doc_text: str, families) -> list:
    expanded = _expand_braces(doc_text)
    missing = []
    for name in families:
        short = name[len(_PREFIX):] if name.startswith(_PREFIX) else name
        if not _documented(expanded, short) \
                and not _documented(expanded, name):
            missing.append(name)
    return missing


def declared_dispatch_kinds():
    """Dispatch kinds announced via obs.declare_kind at import time —
    the compile-cache vocabulary the docs must carry."""
    from nornicdb_tpu.obs.dispatch import bucket_counts

    return sorted(bucket_counts().keys())


def tier_vocabulary():
    """(canonical tier names, normalized degrade reasons) from the
    serving-truth taxonomy (obs/audit.py)."""
    from nornicdb_tpu.obs import audit

    return sorted(audit.ALL_TIERS), sorted(audit.REASONS)


def event_kinds():
    """Incident-timeline event kinds (obs/events.py, ISSUE 13) — the
    /admin/events vocabulary the catalog must carry."""
    from nornicdb_tpu.obs import events

    return sorted(events.KINDS)


def missing_terms(doc_text: str, names) -> list:
    """Vocabulary values (dispatch kinds, tier labels, degrade
    reasons) with no word-boundary mention in the catalog."""
    expanded = _expand_braces(doc_text)
    return [n for n in names if not _documented(expanded, n)]


def tenant_family_drift():
    """(undeclared, stale) — ISSUE 18. A ``tenant`` label is a
    cardinality hazard: every family carrying one must ride the
    capped obs/tenant.py label registry and be declared in
    ``lint.config.TENANT_FAMILIES``. Undeclared = registered family
    with a tenant label but no declaration (the hazard); stale =
    declared name no longer registered (dead declaration)."""
    from nornicdb_tpu.lint.config import TENANT_FAMILIES
    from nornicdb_tpu.obs import REGISTRY

    for mod in IMPORT_TIME_MODULES:
        importlib.import_module(mod)
    carrying = sorted(f.name for f in REGISTRY.families()
                      if "tenant" in f.label_names)
    declared = set(TENANT_FAMILIES)
    undeclared = [n for n in carrying if n not in declared]
    stale = sorted(declared - set(carrying))
    return undeclared, stale


def build_verdict(doc_path: str, repo: str) -> dict:
    """The drift verdict — one dict, shape shared by the standalone
    CLI and the framework pass."""
    families = registered_families()
    with open(doc_path, encoding="utf-8") as f:
        doc_text = f.read()
    missing = missing_from_catalog(doc_text, families)
    # ISSUE 10: the serving-truth vocabularies are part of the catalog
    # contract too — every declared dispatch kind, canonical tier
    # label and normalized degrade reason must be documented
    kinds = declared_dispatch_kinds()
    tiers, reasons = tier_vocabulary()
    events = event_kinds()
    missing_kinds = missing_terms(doc_text, kinds)
    missing_tiers = missing_terms(doc_text, tiers)
    missing_reasons = missing_terms(doc_text, reasons)
    # ISSUE 13: the incident-timeline kinds are catalog contract too —
    # an undocumented /admin/events kind fails the lint like an
    # undocumented tier or reason
    missing_events = missing_terms(doc_text, events)
    # ISSUE 18: tenant-labeled families must be declared in
    # lint.config.TENANT_FAMILIES (the cardinality-cap contract)
    undeclared_tenant, stale_tenant = tenant_family_drift()
    drift = bool(missing or missing_kinds or missing_tiers
                 or missing_reasons or missing_events
                 or undeclared_tenant or stale_tenant)
    return {
        "catalog_lint": True,
        "doc": os.path.relpath(doc_path, repo),
        "families": len(families),
        "dispatch_kinds": len(kinds),
        "tiers": len(tiers),
        "reasons": len(reasons),
        "event_kinds": len(events),
        "missing": missing,
        "missing_kinds": missing_kinds,
        "missing_tiers": missing_tiers,
        "missing_reasons": missing_reasons,
        "missing_events": missing_events,
        "undeclared_tenant": undeclared_tenant,
        "stale_tenant": stale_tenant,
        "verdict": "drift" if drift else "pass",
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Metric-catalog drift lint: every import-time "
                    "metric family must be documented.")
    ap.add_argument("--doc", default=None,
                    help="catalog path (default: docs/observability.md "
                         "next to this repo)")
    ap.add_argument("--list", action="store_true",
                    help="print the import-time families and exit")
    args = ap.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, repo)
    families = registered_families()
    if args.list:
        print(json.dumps(families, indent=1))
        return 0
    doc_path = args.doc or os.path.join(repo, "docs", "observability.md")
    verdict = build_verdict(doc_path, repo)
    print(json.dumps(verdict))
    return 1 if verdict["verdict"] == "drift" else 0


# ---------------------------------------------------------------------------
# framework pass adapter
# ---------------------------------------------------------------------------

def run(tree) -> List:
    """Fifth nornic-lint pass: the drift verdict above rendered as
    findings (one per missing family / vocabulary term)."""
    from nornicdb_tpu.lint import Finding

    doc_rel = "docs/observability.md"
    doc_path = os.path.join(tree.root, doc_rel)
    if not os.path.exists(doc_path):
        return [Finding(
            pass_name=PASS, rule="missing-catalog-doc", path=doc_rel,
            line=1, detail=doc_rel,
            message=f"{doc_rel} not found")]
    verdict = build_verdict(doc_path, tree.root)
    rules = (
        ("missing", "undocumented-metric-family",
         "metric family {0} has no catalog entry"),
        ("missing_kinds", "undocumented-dispatch-kind",
         "dispatch kind {0} has no catalog entry"),
        ("missing_tiers", "undocumented-tier",
         "serving tier {0} has no catalog entry"),
        ("missing_reasons", "undocumented-degrade-reason",
         "degrade reason {0} has no catalog entry"),
        ("missing_events", "undocumented-event-kind",
         "event kind {0} has no catalog entry"),
    )
    findings = []
    for key, rule, msg in rules:
        for name in verdict[key]:
            findings.append(Finding(
                pass_name=PASS, rule=rule, path=doc_rel, line=1,
                detail=name,
                message=msg.format(name)
                + " in docs/observability.md"))
    # tenant-label declarations anchor to the registry file, not the
    # docs — the fix is an edit to lint/config.py
    cfg_rel = "nornicdb_tpu/lint/config.py"
    for name in verdict["undeclared_tenant"]:
        findings.append(Finding(
            pass_name=PASS, rule="undeclared-tenant-family",
            path=cfg_rel, line=1, detail=name,
            message=f"metric family {name} carries a tenant label but "
                    "is not declared in TENANT_FAMILIES "
                    "(cardinality-cap contract, ISSUE 18)"))
    for name in verdict["stale_tenant"]:
        findings.append(Finding(
            pass_name=PASS, rule="stale-tenant-family",
            path=cfg_rel, line=1, detail=name,
            message=f"TENANT_FAMILIES declares {name} but no such "
                    "family is registered"))
    return findings
