"""nornic-lint invariant suite (ISSUE 14): per-pass fixture snippets,
escape hatches, baseline round-trip, CLI gate.

Contract per pass: the injected violation MUST fail the pass, the
escape hatch MUST suppress it, and clean idiomatic code MUST pass.
The final class runs ``scripts/nornic_lint.py`` against the real tree
— the tier-1 gate: a PR introducing any non-baselined violation fails
here.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from nornicdb_tpu import lint  # noqa: E402
from nornicdb_tpu.lint import astutil  # noqa: E402
from nornicdb_tpu.lint import config as lint_cfg  # noqa: E402
from nornicdb_tpu.lint import (  # noqa: E402
    degrade_contract,
    env_catalog,
    jit_hygiene,
    lock_discipline,
)


def _tree(src: str, rel: str = "pkg/mod.py", extra=None, root="/x"):
    sources = {rel: textwrap.dedent(src)}
    if extra:
        sources.update({r: textwrap.dedent(s)
                        for r, s in extra.items()})
    return astutil.parse_sources(root, sources)


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# jit-hygiene
# ---------------------------------------------------------------------------

class TestJitHygiene:
    def test_host_syncs_in_jitted_body_flagged(self):
        tree = _tree("""
            import os
            import functools
            import jax
            import numpy as np

            @functools.partial(jax.jit, static_argnames=("k",))
            def bad(x, k):
                v = x.sum().item()
                f = float(x[0])
                a = np.asarray(x)
                mode = os.environ.get("NORNICDB_MODE", "auto")
                return v + f + a.sum() + len(mode)
        """)
        rules = _rules(jit_hygiene.run(tree))
        assert "host-sync-item" in rules
        assert "host-sync-coercion" in rules
        assert "host-sync-numpy" in rules
        assert "env-read-in-jit" in rules

    def test_wrapped_assignment_and_callees_are_traced(self):
        """X = functools.partial(jax.jit, ...)(impl) marks impl AND
        its module-local callees as traced (trace-time closure)."""
        tree = _tree("""
            import functools
            import jax

            def _helper(x):
                return x.sum().item()

            def _impl(x, k):
                return _helper(x)

            walk = functools.partial(
                jax.jit, static_argnames=("k",))(_impl)
        """)
        fs = jit_hygiene.run(tree)
        assert [f.rule for f in fs] == ["host-sync-item"]
        assert fs[0].context == "_helper"

    def test_static_shape_coercions_are_exempt(self):
        tree = _tree("""
            import jax

            @jax.jit
            def good(x):
                b, d = x.shape
                cap = max(int(1.25 * b / 4), 1)
                n = int(x.shape[0])
                m = float(len(x.shape))
                return x[:cap] * n * m
        """)
        assert jit_hygiene.run(tree) == []

    def test_escape_hatch_suppresses(self):
        tree = _tree("""
            import jax

            @jax.jit
            def gated(x):
                return x.sum().item()  # lint: jit-ok
        """)
        assert jit_hygiene.run(tree) == []

    def test_unbucketed_dispatch_flagged_pow2_literal_ok(self):
        tree = _tree("""
            from nornicdb_tpu.obs.dispatch import record_dispatch
            from nornicdb_tpu.search.microbatch import pow2_bucket

            def dispatch(rows, k, dt):
                b = len(rows)
                record_dispatch("kindA", b, k, dt)          # raw: flag
                record_dispatch("kindB", 1, k, dt)          # pow2 lit
                record_dispatch("kindC", 48, k, dt)         # non-pow2
                bb = pow2_bucket(max(b, 1))
                record_dispatch("kindD", bb, k, dt)         # bucketed
                record_dispatch("kindE", pow2_bucket(b), k, dt)
        """)
        fs = jit_hygiene.run(tree)
        assert _rules(fs) == ["unbucketed-dispatch",
                              "unbucketed-dispatch"]
        assert sorted(f.detail for f in fs) == ["48", "b"]


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

_LOCKED_CLASS = """
    import threading

    class Index:
        def __init__(self):
            self._lock = threading.Lock()
            self.mutations = 0     # init writes are exempt

        def add(self, v):
            with self._lock:
                self.mutations += 1

        def _compact_locked(self):
            self.mutations += 1    # _locked convention: caller holds

        def sneak(self):
            self.mutations += 1{hatch}
"""


class TestLockDiscipline:
    def test_unguarded_write_flagged(self):
        tree = _tree(_LOCKED_CLASS.format(hatch=""))
        fs = lock_discipline.run(tree)
        assert _rules(fs) == ["unguarded-write"]
        assert fs[0].context == "Index.sneak"
        assert fs[0].detail == "mutations"

    def test_escape_hatch_suppresses(self):
        tree = _tree(
            _LOCKED_CLASS.format(hatch="  # lint: unguarded-ok"))
        assert lock_discipline.run(tree) == []

    def test_never_guarded_attr_not_flagged(self):
        tree = _tree("""
            import threading

            class Plain:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.hint = 0

                def poke(self):
                    self.hint += 1   # never lock-guarded anywhere
        """)
        assert lock_discipline.run(tree) == []

    def test_fingerprint_is_line_stable(self):
        a = _tree(_LOCKED_CLASS.format(hatch=""))
        b = _tree("# a new comment shifts every line\n"
                  + textwrap.dedent(_LOCKED_CLASS.format(hatch="")))
        fa, = lock_discipline.run(a)
        fb, = lock_discipline.run(b)
        assert fa.fingerprint() == fb.fingerprint()
        assert fa.line != fb.line


# ---------------------------------------------------------------------------
# degrade-contract
# ---------------------------------------------------------------------------

_AUDIT_STUB = """
    REASONS = ("underfill", "error", "replica_lag", "replica_drain")
    _LEGACY_REASONS = {"walk_underfill_brute": "underfill"}
"""


def _degrade_tree(body: str):
    return _tree(
        body, rel="pkg/serving.py",
        extra={"nornicdb_tpu/obs/audit.py": _AUDIT_STUB})


class TestDegradeContract:
    @pytest.fixture(autouse=True)
    def _fixture_registry(self, monkeypatch):
        # fixture trees don't contain the real snapshot modules; the
        # recheck test installs its own registry on top of this
        monkeypatch.setattr(lint_cfg, "SNAPSHOT_MODULES", {})

    def test_unknown_reason_literal_flagged(self):
        tree = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def serve():
                _audit.record_degrade("vector", "a", "b", "underfill")
                _audit.record_degrade("vector", "a", "b", "made_up")
                _audit.record_degrade(
                    "vector", "a", "b", "walk_underfill_brute")
        """)
        fs = degrade_contract.run(tree)
        assert _rules(fs) == ["unknown-degrade-reason"]
        assert fs[0].detail == "made_up"

    def test_wrapper_propagation_checks_call_sites(self):
        tree = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def _ledger(from_tier, reason, versions=None):
                _audit.record_degrade(
                    "graph", from_tier, "host", reason)

            def serve():
                _ledger("tier_a", "underfill")
                _ledger("tier_a", "invented_reason")
        """)
        fs = degrade_contract.run(tree)
        assert _rules(fs) == ["unknown-degrade-reason"]
        assert fs[0].detail == "invented_reason"

    def test_conditional_local_literals_resolve(self):
        tree = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def drain(reason_text):
                r = ("replica_lag"
                     if reason_text.startswith("replica_lag")
                     else "replica_drain")
                _audit.record_degrade("fleet", "replica", "primary", r)
        """)
        assert degrade_contract.run(tree) == []

    def test_none_guard_idiom_resolves(self):
        """ISSUE 15: the admission-hold pattern — ``hold = None`` plus
        conditional literal assignments guarded by ``if hold is not
        None`` — resolves to its literal values (the bare None arm is
        the no-degrade path, skipped rather than unresolvable)."""
        tree = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def gate(tier):
                hold = None
                if not _audit.tier_allowed(tier):
                    hold = "underfill"
                elif not _audit.admission_allows(tier):
                    hold = "error"
                if hold is not None:
                    _audit.record_degrade("vector", tier, "brute", hold)
        """)
        assert degrade_contract.run(tree) == []

    def test_none_guard_idiom_still_flags_unknown_literals(self):
        tree = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def gate(tier):
                hold = None
                if tier:
                    hold = "not_a_reason"
                if hold is not None:
                    _audit.record_degrade("vector", tier, "brute", hold)
        """)
        assert _rules(degrade_contract.run(tree)) == [
            "unknown-degrade-reason"]

    def test_dynamic_reason_flagged_and_hatch_suppresses(self):
        tree = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def serve(obj):
                _audit.record_degrade(
                    "vector", "a", "b", obj.reason_attr)
        """)
        assert _rules(degrade_contract.run(tree)) == [
            "dynamic-degrade-reason"]
        hatch = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def serve(obj):
                _audit.record_degrade(  # lint: degrade-ok
                    "vector", "a", "b", obj.reason_attr)
        """)
        assert degrade_contract.run(hatch) == []
        # literal reason two lines below a call-line hatch: suppressed
        # (the documented "on or one line above" contract covers the
        # call line of a multi-line call too)
        hatch_literal = _degrade_tree("""
            from nornicdb_tpu.obs import audit as _audit

            def serve():
                _audit.record_degrade(  # lint: degrade-ok
                    "vector", "a", "b",
                    "not_in_vocab_but_hatched")
        """)
        assert degrade_contract.run(hatch_literal) == []

    def test_missing_version_recheck(self, monkeypatch):
        monkeypatch.setattr(
            lint_cfg, "SNAPSHOT_MODULES",
            {"pkg.snapmod": ("Plane._decode",)})
        ok = _tree("""
            class Plane:
                def _decode(self, snap):
                    if self.catalog.version != snap["version"]:
                        return None
                    return snap
        """, rel="pkg/snapmod.py",
            extra={"nornicdb_tpu/obs/audit.py": _AUDIT_STUB})
        assert degrade_contract.run(ok) == []
        # the re-check compare removed: the registered carrier fails
        bad = _tree("""
            class Plane:
                def _decode(self, snap):
                    return snap
        """, rel="pkg/snapmod.py",
            extra={"nornicdb_tpu/obs/audit.py": _AUDIT_STUB})
        assert _rules(degrade_contract.run(bad)) == [
            "missing-version-recheck"]
        # carrier renamed away entirely: also fails (registry must
        # follow renames, reviewed like code)
        gone = _tree("class Plane:\n    pass\n",
                     rel="pkg/snapmod.py",
                     extra={"nornicdb_tpu/obs/audit.py": _AUDIT_STUB})
        assert _rules(degrade_contract.run(gone)) == [
            "missing-version-recheck"]


# ---------------------------------------------------------------------------
# env-knob-catalog
# ---------------------------------------------------------------------------

class TestEnvKnobCatalog:
    def _run(self, tmp_path, src, doc_text, rel="pkg/mod.py"):
        doc = tmp_path / "docs" / "configuration.md"
        doc.parent.mkdir(exist_ok=True)
        doc.write_text(doc_text)
        tree = _tree(src, rel=rel, root=str(tmp_path))
        return env_catalog.run(tree)

    def test_undocumented_knob_flagged(self, tmp_path):
        src = """
            import os

            MODE = os.environ.get("NORNICDB_NEW_KNOB", "off")
        """
        fs = self._run(tmp_path, src, "nothing here")
        assert _rules(fs) == ["undocumented-env-knob"]
        assert fs[0].detail == "NORNICDB_NEW_KNOB"
        assert self._run(
            tmp_path, src, "knob `NORNICDB_NEW_KNOB` does X") == []

    def test_prefixing_helper_resolves_short_name(self, tmp_path):
        src = """
            from nornicdb_tpu.config import env_bool

            FLAG = env_bool("SHINY_FEATURE", True)
        """
        fs = self._run(tmp_path, src, "")
        assert [f.detail for f in fs] == ["NORNICDB_SHINY_FEATURE"]

    def test_hot_path_read_flagged_and_hatch(self, tmp_path,
                                             monkeypatch):
        monkeypatch.setattr(
            lint_cfg, "HOT_PATHS",
            (("pkg/mod.py", "Plane.serve"),))
        doc = "`NORNICDB_GATE` documented"
        hot = """
            import os

            class Plane:
                def serve(self, q):
                    return os.environ.get("NORNICDB_GATE", "auto")
        """
        fs = self._run(tmp_path, hot, doc)
        assert _rules(fs) == ["env-read-on-hot-path"]
        assert fs[0].context == "Plane.serve"
        hatched = hot.replace(
            'return os.environ.get("NORNICDB_GATE", "auto")',
            'return os.environ.get(  # lint: env-ok\n'
            '                "NORNICDB_GATE", "auto")')
        assert self._run(tmp_path, hatched, doc) == []

    def test_env_write_is_not_a_read(self, tmp_path):
        """os.environ["X"] = v is a WRITE (cli.py overrides knobs this
        way) — it must not land in the catalog or hot-path findings."""
        src = """
            import os

            def configure(v):
                os.environ["NORNICDB_WRITTEN_ONLY"] = v
        """
        tree = _tree(src, root=str(tmp_path))
        assert env_catalog.catalog(tree) == {}

    def test_catalog_render_and_write_roundtrip(self, tmp_path):
        src = """
            import os

            A = os.environ.get("NORNICDB_ALPHA")
            B = os.getenv("NORNICDB_BETA", "1")
        """
        tree = _tree(src, root=str(tmp_path))
        cat = env_catalog.catalog(tree)
        assert set(cat) == {"NORNICDB_ALPHA", "NORNICDB_BETA"}
        doc = tmp_path / "docs" / "configuration.md"
        doc.parent.mkdir(exist_ok=True)
        doc.write_text("# prose head\n\n"
                       + env_catalog.CATALOG_BEGIN + "\nstale\n"
                       + env_catalog.CATALOG_END + "\n\nprose tail\n")
        env_catalog.write_catalog(tree, str(doc))
        text = doc.read_text()
        assert "# prose head" in text and "prose tail" in text
        assert "stale" not in text
        assert "NORNICDB_ALPHA" in text and "NORNICDB_BETA" in text
        # regeneration is idempotent
        env_catalog.write_catalog(tree, str(doc))
        assert doc.read_text() == text


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_round_trip_and_count_semantics(self, tmp_path):
        tree = _tree(_LOCKED_CLASS.format(hatch=""))
        findings = lock_discipline.run(tree)
        assert len(findings) == 1
        path = str(tmp_path / "baseline.json")
        lint.save_baseline(path, findings)
        baseline = lint.load_baseline(path)
        # clean round-trip: everything baselined
        assert lint.apply_baseline(findings, baseline) == []
        # a SECOND violation with the same fingerprint is fresh
        doubled = findings + findings
        fresh = lint.apply_baseline(doubled, baseline)
        assert len(fresh) == 1
        # missing file = strict empty baseline
        assert lint.load_baseline(str(tmp_path / "nope.json")) == {}

    def test_repo_baseline_is_committed_and_clean(self):
        path = os.path.join(REPO, lint.DEFAULT_BASELINE)
        assert os.path.exists(path), (
            "scripts/nornic_lint_baseline.json must be committed")
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        assert data["version"] == 1
        # the ISSUE 14 sweep fixed every finding instead of
        # grandfathering: keep it that way (additions need review)
        assert data["findings"] == {}


# ---------------------------------------------------------------------------
# CLI / tier-1 gate
# ---------------------------------------------------------------------------

class TestCli:
    def test_list_passes(self):
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "nornic_lint.py"),
             "--list-passes", "--json"],
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stdout + out.stderr
        table = json.loads(out.stdout)
        assert set(table) == {
            "jit-hygiene", "lock-discipline", "degrade-contract",
            "env-knob-catalog", "metrics-catalog"}
        assert all(table.values())

    def test_tree_is_clean(self):
        """THE tier-1 gate: all five passes over the real tree, zero
        non-baselined findings. A PR that introduces a violation (or
        reads a new env knob without documenting it) fails here."""
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "nornic_lint.py"),
             "--json"],
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stdout + out.stderr
        verdict = json.loads(out.stdout.strip().splitlines()[-1])
        assert verdict["nornic_lint"] is True
        assert verdict["verdict"] == "pass"
        assert verdict["fresh"] == []
        assert set(verdict["passes"]) == {
            "jit-hygiene", "lock-discipline", "degrade-contract",
            "env-knob-catalog", "metrics-catalog"}
        # the one-line shape tooling consumes
        for key in ("files", "baseline", "total", "fresh_total"):
            assert key in verdict

    def test_injected_violation_fails_subset_run(self, tmp_path):
        """--root at a synthetic mini-repo: violation -> exit 1 with
        the finding in --json; --update-baseline then grandfathers it
        (baseline round-trip through the real CLI)."""
        pkg = tmp_path / "nornicdb_tpu"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "mod.py").write_text(textwrap.dedent("""
            import threading

            class Idx:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def a(self):
                    with self._lock:
                        self.n += 1

                def b(self):
                    self.n += 1
        """))
        cli = os.path.join(REPO, "scripts", "nornic_lint.py")
        args = [sys.executable, cli, "--root", str(tmp_path),
                "--passes", "lock-discipline",
                "--baseline", str(tmp_path / "base.json"), "--json"]
        out = subprocess.run(args, capture_output=True, text=True,
                             cwd=REPO)
        assert out.returncode == 1, out.stdout + out.stderr
        verdict = json.loads(out.stdout)
        assert verdict["verdict"] == "violations"
        assert verdict["fresh"][0]["rule"] == "unguarded-write"
        # seed the baseline with another pass's grandfathered entry:
        # a subset --update-baseline must PRESERVE it, not drop it
        other_fp = "jit-hygiene|host-sync-item|x.py|f|x.item()"
        (tmp_path / "base.json").write_text(json.dumps(
            {"version": 1, "findings": {other_fp: 1}}))
        # --update-baseline, then the same run is clean
        subprocess.run(
            [sys.executable, cli, "--root", str(tmp_path),
             "--passes", "lock-discipline",
             "--baseline", str(tmp_path / "base.json"),
             "--update-baseline"],
            capture_output=True, text=True, cwd=REPO, check=True)
        merged = json.loads((tmp_path / "base.json").read_text())
        assert other_fp in merged["findings"], merged
        out2 = subprocess.run(args, capture_output=True, text=True,
                              cwd=REPO)
        assert out2.returncode == 0, out2.stdout + out2.stderr
        assert json.loads(out2.stdout)["verdict"] == "pass"


# ---------------------------------------------------------------------------
# deadlock watchdog fixture
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_watchdog_dumps_stacks_on_hang(self, tmp_path):
        """NORNICDB_TEST_WATCHDOG_S=1: a test hanging past the budget
        gets all-thread stacks on stderr and the run dies fast instead
        of eating tier-1's whole timeout."""
        (tmp_path / "test_hang.py").write_text(textwrap.dedent("""
            import threading

            def test_deadlock_stand_in():
                lock = threading.Lock()
                lock.acquire()
                lock.acquire()   # classic self-deadlock
        """))
        # the watchdog lives in tests/conftest.py; re-export it so the
        # isolated tmp run arms the same fixture (loaded by path — a
        # bare ``import conftest`` would hit THIS conftest circularly)
        repo_conftest = os.path.join(REPO, "tests", "conftest.py")
        (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""
            import importlib.util

            _spec = importlib.util.spec_from_file_location(
                "_repo_conftest", {repo_conftest!r})
            _mod = importlib.util.module_from_spec(_spec)
            _spec.loader.exec_module(_mod)
            _deadlock_watchdog = _mod._deadlock_watchdog
        """))
        env = dict(os.environ)
        env["NORNICDB_TEST_WATCHDOG_S"] = "1"
        env["NORNICDB_TEST_WATCHDOG_EXIT"] = "1"
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q", "-s", "-p",
             "no:cacheprovider", "test_hang.py"],
            capture_output=True, text=True, timeout=120,
            cwd=str(tmp_path), env=env)
        assert out.returncode != 0
        assert "Timeout" in out.stderr or "Thread" in out.stderr, (
            out.stdout + out.stderr)
        assert "test_deadlock_stand_in" in out.stderr

    def test_watchdog_off_by_default(self):
        import faulthandler

        if os.environ.get("NORNICDB_TEST_WATCHDOG_S"):
            pytest.skip("watchdog deliberately armed for this run")
        # the autouse fixture armed nothing for THIS test
        faulthandler.cancel_dump_traceback_later()  # no-op if unarmed
