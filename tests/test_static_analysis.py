"""graftlint — the static-analysis subsystem (ISSUE 13).

Acceptance contract: the whole repo lints clean (every pass, zero
non-allowlisted findings) — THE tier-1 gate, mirrored by the fast lint
stage in ``scripts/tier1.sh``; the analyzer itself never imports jax; every
rule has a positive fixture proving it still fires (a rule without a
failing fixture silently rots); the allowlist round-trips (suppression,
mandatory justification, stale-entry and malformed-file detection); the
threaded modules carry their ``# guarded-by:`` annotations; and the lock
fixes this PR landed (locked instrument reads, locked flight-recorder
introspection) hold under a thread hammer.
"""

import functools
import json
import os
import re
import subprocess
import sys
import threading

import pytest

from veomni_tpu.analysis import run_lint
from veomni_tpu.analysis.core import Allowlist, RepoIndex

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_REPO, "tests", "tools", "lint_fixtures")

_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([a-z-]+/[a-z-]+)")


# ------------------------------------------------------------ the tier-1 gate
def test_repo_lints_clean():
    """Every pass over the whole repo: zero non-allowlisted findings.

    This is the gate ISSUE 13 ships green: real violations found while
    building it were either fixed (locked metric/recorder reads, serve.py
    health endpoint off live scheduler state, doc tables for every knob/
    op) or allowlisted with a justification."""
    result = run_lint(_REPO)
    assert result.ok, "\n".join(f.format() for f in result.findings)


def test_lint_cli_json_fast_and_jax_free(tmp_path):
    """The CLI exits 0 on the clean repo, emits the CI JSON artifact, and
    asserts internally that jax was never imported (the tier-1 lint stage
    depends on exactly that property to run in seconds)."""
    out = str(tmp_path / "lint.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "lint.py"),
         "--json", out],
        capture_output=True, text=True, timeout=120, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.load(open(out))
    assert doc["ok"] is True
    assert doc["findings"] == []
    assert doc["elapsed_s"] < 60.0
    assert "no JAX" in proc.stderr


def test_analysis_package_imports_without_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import veomni_tpu.analysis, sys; "
         "assert 'jax' not in sys.modules, 'analysis pulled in jax'"],
        capture_output=True, text=True, timeout=60, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------------------------- fixtures
def _expectations(root):
    """{(relpath, line): rule} from # EXPECT: markers in a fixture tree."""
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            full = os.path.join(dirpath, fname)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            for lineno, line in enumerate(open(full), 1):
                m = _EXPECT_RE.search(line)
                if m:
                    out[(rel, lineno)] = m.group(1)
    return out


def _assert_exact(findings, expected, rule_prefixes):
    """Every EXPECT fires on its line; no unexpected finding under the
    checked rule families (both directions — silent extra findings would
    mean the rule over-triggers on clean fixture code)."""
    got = {}
    for f in findings:
        if any(f.rule.startswith(p) for p in rule_prefixes):
            got.setdefault((f.path, f.line), set()).add(f.rule)
    missing = {
        k: rule for k, rule in expected.items()
        if any(rule.startswith(p) for p in rule_prefixes)
        and rule not in got.get(k, set())
    }
    assert not missing, f"fixture rules did not fire: {missing}; got {got}"
    unexpected = {
        k: rules for k, rules in got.items()
        if expected.get(k) not in rules
    }
    assert not unexpected, f"unexpected findings on clean lines: {unexpected}"


def test_purity_and_recompile_fixtures_fire():
    from veomni_tpu.analysis import purity, recompile

    root = os.path.join(_FIXTURES, "repo")
    index = RepoIndex.load(root)
    expected = _expectations(root)
    findings = purity.run(index) + recompile.run(index)
    _assert_exact(findings, expected, ("trace-purity", "recompile-hazard"))
    # the sanctioned TRACE_COUNTS bump line produced NO finding at all
    hot = open(os.path.join(root, "veomni_tpu", "hot.py")).read().splitlines()
    counts_line = next(i for i, l in enumerate(hot, 1)
                       if "TRACE_COUNTS[" in l)
    assert not any(f.line == counts_line for f in findings)


def test_lock_discipline_fixtures_fire():
    from veomni_tpu.analysis import locks

    root = os.path.join(_FIXTURES, "repo")
    index = RepoIndex.load(root)
    expected = _expectations(root)
    _assert_exact(locks.run(index), expected, ("lock-discipline",))


def test_drift_fixtures_fire():
    from veomni_tpu.analysis import drift

    root = os.path.join(_FIXTURES, "drift_repo")
    index = RepoIndex.load(root)
    expected = _expectations(root)
    findings = (drift.metric_findings(index) + drift.knob_findings(index)
                + drift.env_findings(index) + drift.fault_findings(index)
                + drift.registry_findings(index))
    _assert_exact(findings, expected, ("drift/",))


def test_traced_walk_reaches_known_roots():
    """The purity pass's sanity pins, asserted directly: losing a decode/
    engine/train-step root would make the whole family vacuous."""
    from veomni_tpu.analysis.callgraph import get_callgraph
    from veomni_tpu.analysis.purity import SANITY_TRACED

    index = RepoIndex.load(_REPO)
    seen = {
        (tf.func.sf.path, tf.func.qualname)
        for tf in get_callgraph(index).traced_functions().values()
    }
    missing = SANITY_TRACED - seen
    assert not missing, f"traced walk lost roots: {sorted(missing)}"


# ------------------------------------------------------------------ allowlist
def test_allowlist_roundtrip(tmp_path):
    from veomni_tpu.analysis import purity

    root = os.path.join(_FIXTURES, "repo")
    index = RepoIndex.load(root)
    target = next(f for f in purity.run(index)
                  if f.rule == "trace-purity/host-time")
    allow = tmp_path / "allow.toml"
    allow.write_text(
        "[[allow]]\n"
        f'rule = "{target.rule}"\n'
        f'path = "{target.path}"\n'
        'match = "impure_step"\n'
        'justification = "fixture roundtrip"\n'
    )
    al = Allowlist.load(str(allow))
    kept = al.filter([target])
    assert kept == [] and al.entries[0].hits == 1
    assert al.audit() == []  # matched + justified: no policy findings


def test_allowlist_stale_and_missing_justification(tmp_path):
    allow = tmp_path / "allow.toml"
    allow.write_text(
        "[[allow]]\n"
        'rule = "trace-purity/host-time"\n'
        'path = "veomni_tpu/nonexistent.py"\n'
        'justification = "excuses code that no longer exists"\n'
        "\n"
        "[[allow]]\n"
        'rule = "trace-purity/io"\n'
        'path = "veomni_tpu/also_missing.py"\n'
        'justification = ""\n'
    )
    al = Allowlist.load(str(allow))
    al.filter([])  # nothing matches anything
    rules = sorted(f.rule for f in al.audit())
    assert rules == ["allowlist/missing-justification",
                     "allowlist/stale-entry", "allowlist/stale-entry"]


def test_allowlist_malformed_fails_loudly(tmp_path):
    allow = tmp_path / "allow.toml"
    allow.write_text("[allow]\nrule = broken\n")
    al = Allowlist.load(str(allow))
    assert any(f.rule == "allowlist/malformed" for f in al.audit())


def test_repo_allowlist_policy():
    """The real allowlist parses, and every entry carries a justification
    (stale entries are covered by test_repo_lints_clean — a stale entry IS
    a finding)."""
    al = Allowlist.load(os.path.join(_REPO, "veomni_tpu", "analysis",
                                     "allowlist.toml"))
    assert not al.errors
    for e in al.entries:
        assert e.justification.strip(), f"{e.rule} @ {e.path} unjustified"


# ------------------------------------------- annotations + lock-fix regression
ANNOTATED_MODULES = (
    "veomni_tpu/observability/metrics.py",
    "veomni_tpu/observability/spans.py",
    "veomni_tpu/observability/flight_recorder.py",
    "veomni_tpu/observability/request_trace.py",
    "veomni_tpu/observability/fleet.py",
)


def test_threaded_modules_carry_guard_annotations():
    """ISSUE 13 satellite: the threaded observability modules declare their
    lock contracts. An annotation deleted along with a refactor silently
    removes its enforcement — this pins the coverage."""
    from veomni_tpu.analysis import locks

    index = RepoIndex.load(_REPO)
    for path in ANNOTATED_MODULES:
        anns = locks._comment_annotations(index.files[path])
        assert anns, f"{path} lost its # guarded-by: annotations"


def test_metrics_value_reads_are_locked_under_hammer():
    """Regression for the unlocked instrument reads the lock-discipline
    pass found: Counter.value / Histogram.count/sum and registry get() now
    take the shared lock, so a reader thread always observes a consistent
    (count, sum) pair mid-hammer."""
    from veomni_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("hammer.h")
    stop = threading.Event()
    errs = []

    def writer():
        while not stop.is_set():
            h.observe(1.0)

    def reader():
        while not stop.is_set():
            c, s = h.count, h.sum
            # sum of N observations of exactly 1.0 can never exceed the
            # count observed AFTER it — torn reads would break this
            if s > h.count + 1e-9:
                errs.append((c, s))
            reg.get("hammer.h")
            reg.histogram_sum("hammer.h")

    threads = [threading.Thread(target=writer) for _ in range(2)] + [
        threading.Thread(target=reader) for _ in range(2)
    ]
    for t in threads:
        t.start()
    stop_timer = threading.Timer(0.5, stop.set)
    stop_timer.start()
    for t in threads:
        t.join(timeout=10)
    stop_timer.cancel()
    stop.set()
    assert not errs, f"torn histogram reads: {errs[:3]}"
    assert h.count == pytest.approx(h.sum)


def test_flight_recorder_len_dropped_consistent_under_hammer():
    """Regression for the unlocked ``__len__``/``dropped`` reads: with a
    ring of capacity N, a reader must never observe len > N, and the
    snapshot's (events, dropped) pair comes from one locked pass."""
    from veomni_tpu.observability.flight_recorder import FlightRecorder

    rec = FlightRecorder(max_events=64)
    stop = threading.Event()
    errs = []

    def writer():
        i = 0
        while not stop.is_set():
            rec.record("hammer", cid=str(i))
            i += 1

    def reader():
        while not stop.is_set():
            if len(rec) > 64:
                errs.append(len(rec))
            snap = rec.snapshot(limit=8)
            if snap["dropped"] < 0:
                errs.append(snap["dropped"])

    threads = [threading.Thread(target=writer) for _ in range(2)] + [
        threading.Thread(target=reader)
    ]
    for t in threads:
        t.start()
    stop_timer = threading.Timer(0.4, stop.set)
    stop_timer.start()
    for t in threads:
        t.join(timeout=10)
    stop_timer.cancel()
    stop.set()
    assert not errs
    assert len(rec) <= 64 and rec.dropped >= 0


# ------------------------------------------------------- the documents' paths
_DOCUMENTS = (
    ["README.md", os.path.join(".claude", "skills", "verify", "SKILL.md")]
    + sorted(os.path.join("docs", n)
             for n in os.listdir(os.path.join(_REPO, "docs"))
             if n.endswith(".md"))
)
_PATH_RE = re.compile(r"[\w.<>*{}$/-]*[\w>*}]\.(?:py|sh|yaml|json)\b")
# docs/MIGRATION.md's left column names the REFERENCE project's files
_THE_REFERENCES = {"train.py", "tasks/train_torch.py",
                   "multimodal_chat_template.py"}


@functools.lru_cache(maxsize=None)
def _repo_basenames():
    names = set()
    for dirpath, dirnames, filenames in os.walk(_REPO):
        dirnames[:] = [d for d in dirnames
                       if not d.startswith(".") and d not in (
                           "__pycache__", "output", "chiprun_out",
                           "archive_check")]
        names.update(filenames)
    return frozenset(names)


@pytest.mark.parametrize("doc", _DOCUMENTS)
def test_documents_name_only_files_that_exist(doc):
    """Every repo path ending in .py, .sh, .yaml or .json that a document
    names exists, and no document mentions a ``BENCH_`` switch: the harness
    that read them is gone, and a document that teaches a dead entry point
    costs every reader after it. A path with a slash is looked up from the
    repo root, then from ``veomni_tpu/`` (the documents write
    ``models/transformer.py``), where its first directory is one of theirs
    (``global_step_N/manifest.json`` is a run's artifact); a bare .py or .sh
    name must be some file's name. Placeholders (``<cell>``, ``*``, ``{}``,
    ``$``), absolute paths and bare .json/.yaml names (run artifacts,
    examples) are not paths of the repo."""
    text = open(os.path.join(_REPO, doc), encoding="utf-8").read()
    assert not re.search(r"BENCH_[A-Z]", text), (
        f"{doc} still names a BENCH_* switch")
    basenames = _repo_basenames()
    missing = []
    for m in _PATH_RE.finditer(text):
        name = m.group(0)
        if (re.search(r"[<>*{}$]", name) or name.startswith("/")
                or name in _THE_REFERENCES):
            continue
        name = name.lstrip("./") if name.startswith(("./", "../")) else name
        if "/" not in name:
            if name.endswith((".py", ".sh")) and name not in basenames:
                missing.append(name)
            continue
        bases = [b for b in ("", "veomni_tpu") if os.path.isdir(
            os.path.join(_REPO, b, name.split("/")[0]))]
        if bases and not any(os.path.exists(os.path.join(_REPO, b, name))
                             for b in bases):
            missing.append(name)
    assert not missing, f"{doc} names files that do not exist: {missing}"
