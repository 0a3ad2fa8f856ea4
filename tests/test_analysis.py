"""areal-lint tier-1 suite.

One test per rule against the seeded known-bad fixtures under
tests/fixtures/lint/, the pragma/baseline semantics, the repo-wide
clean-against-baseline gate (the acceptance invariant:
`python -m areal_tpu.analysis areal_tpu/` exits 0), and a regression test
reproducing the PR 3 zero-copy alias hazard pattern.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from areal_tpu.analysis import Baseline, analyze_paths
from areal_tpu.analysis.core import RULES

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "lint"


def _codes(findings):
    return {f.rule for f in findings}


def _run_fixture(name):
    return analyze_paths([str(FIXTURES / name)])


# -- one test per rule -------------------------------------------------------


def test_ar101_unguarded_multi_context_write():
    fs = _run_fixture("ar101_unguarded.py")
    assert _codes(fs) == {"AR101"}
    (f,) = fs
    assert f.key == "Worker._counter"
    # negative space: the queue attr, the lock-guarded attr and the
    # registry-declared attr must NOT fire
    assert "_safe_q" not in f.message
    assert all("locked_total" not in x.key and "_fenced" not in x.key for x in fs)


def test_ar102_lock_order_cycle():
    fs = _run_fixture("ar102_cycle.py")
    assert _codes(fs) == {"AR102"}
    (f,) = fs
    assert "Pipeline._a" in f.key and "Pipeline._b" in f.key


def test_ar103_rank_violation():
    fs = _run_fixture("ar103_rank.py")
    assert _codes(fs) == {"AR103"}
    (f,) = fs
    assert f.key == "Ranked._high->Ranked._low"


def test_ar104_unknown_guard():
    fs = _run_fixture("ar104_unknown_guard.py")
    assert _codes(fs) == {"AR104"}
    keys = {f.key for f in fs}
    assert keys == {
        "Annotated._registry_attr",
        "NoSuchClass._x",
        "Annotated._bad",
    }


def test_ar201_host_sync_in_loop():
    fs = _run_fixture("ar201_host_sync.py")
    assert _codes(fs) == {"AR201"}
    # .item(), float(), np.asarray() — one finding each, all inside the loop
    assert len(fs) == 3
    assert {f.line for f in fs} == {18, 19, 20}


def test_ar202_donated_buffer_reuse():
    fs = _run_fixture("ar202_donated.py")
    assert _codes(fs) == {"AR202"}
    (f,) = fs
    assert f.key == "bad.state"  # good() rebinding must not fire


def test_ar203_alias_upload():
    fs = _run_fixture("ar203_alias.py")
    assert _codes(fs) == {"AR203"}
    keys = {f.key for f in fs}
    # local pattern AND the cross-method self-attribute pattern; the
    # explicit-copy variant must not fire
    assert keys == {
        "upload_then_mutate.lengths",
        "Engine.self._slot_lengths",
    }


def test_ar204_retrace_hazards():
    fs = _run_fixture("ar204_retrace.py")
    assert _codes(fs) == {"AR204"}
    keys = {f.key for f in fs}
    assert keys == {"bad_loop.step.arg1", "bad_static.bucketed.arg1"}


def test_ar106_swallowed_exceptions():
    fs = _run_fixture("ar106_swallow.py")
    assert _codes(fs) == {"AR106"}
    keys = {f.key for f in fs}
    # the four swallow shapes fire; re-raise / log / preserve / narrow
    # escapes must not
    assert keys == {
        "swallow_pass.except#0",
        "swallow_bare.except#0",
        "swallow_busy.except#0",
        "swallow_tuple.except#0",
    }


def test_ar106_scoped_to_fault_bearing_packages(tmp_path):
    """AR106 runs only over areal_tpu/{core,launcher,engine}/ — a swallow
    in, say, utils/ (the retry loop's home) is out of scope; a fixture
    outside the areal_tpu tree is always checked."""
    src = textwrap.dedent(
        """
        def f(x):
            try:
                return 1 / x
            except Exception:
                pass
        """
    )
    tree = tmp_path / "areal_tpu"
    for pkg, expect in [("core", True), ("utils", False), ("models", False)]:
        d = tree / pkg
        d.mkdir(parents=True)
        mod = d / "mod.py"
        mod.write_text(src)
        fs = [f for f in analyze_paths([str(mod)]) if f.rule == "AR106"]
        assert bool(fs) == expect, (pkg, fs)


def test_ar106_pragma_suppresses():
    import tempfile, os

    src = (
        "def f(x):\n"
        "    try:\n"
        "        return 1 / x\n"
        "    except Exception:  # areal-lint: disable=AR106\n"
        "        pass\n"
    )
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "frag.py")
        with open(p, "w") as fh:
            fh.write(src)
        assert not [f for f in analyze_paths([p]) if f.rule == "AR106"]


def test_ar301_route_pairing():
    fs = _run_fixture("ar301_routes.py")
    assert _codes(fs) == {"AR301"}
    assert {f.key for f in fs} == {"/missing", "/dead_route"}
    # negative space: the paired route, the `# wire: external` route, and
    # the f-string ref with a query string must all stay clean
    assert not any("paired" in f.key or "ops_surface" in f.key for f in fs)


def test_ar301_client_only_sweep_stays_quiet(tmp_path):
    """No registrations harvested -> pairing cannot be judged; a
    client-only sweep must not drown in unregistered-path noise."""
    mod = tmp_path / "client.py"
    mod.write_text(
        "async def poll(arequest_with_retry, addr):\n"
        "    return await arequest_with_retry(addr, '/not_registered')\n"
    )
    assert not [f for f in analyze_paths([str(mod)]) if f.rule == "AR301"]


def test_ar302_seam_validity():
    fs = _run_fixture("ar302_seams.py")
    assert _codes(fs) == {"AR302"}
    # the typo'd FaultPoint AND the embedded {"site": ...} plan fire; the
    # kv.* pattern that matches real seams must not
    assert {f.key for f in fs} == {"kv.sendd", "weight.push.*"}


def test_ar302_seam_collision(tmp_path):
    """One seam name fired from two modules: a single fnmatch pattern now
    perturbs two unrelated boundaries."""
    for mod in ("a", "b"):
        (tmp_path / f"{mod}.py").write_text(
            "from areal_tpu.core import fault_injection\n"
            "def go():\n"
            "    fault_injection.fire('shared.seam')\n"
        )
    fs = [f for f in analyze_paths([str(tmp_path)]) if f.rule == "AR302"]
    assert len(fs) == 1 and fs[0].key == "shared.seam"


def test_ar303_metrics_contract():
    fs = _run_fixture("ar303_metrics.py")
    assert _codes(fs) == {"AR303"}
    keys = {f.key for f in fs}
    # counter drift + undeclared *_KEYS entry + unproduced consumer read;
    # the declared counter, the produced poll key, and the produced
    # consumer read must not fire
    assert keys == {
        "Server._req_stats[rejectd]",
        "POLL_KEYS.kv_occupancy",
        "autoscale.prefill_lag",
    }


def test_ar304_stale_registry():
    fs = _run_fixture("ar304_stale_registry.py")
    assert _codes(fs) == {"AR304"}
    (f,) = fs
    # the still-live entry must not fire
    assert f.key == "Tracker._retired_attr"


def test_ar305_knob_drift():
    fs = _run_fixture("ar305_knob_drift.py")
    assert _codes(fs) == {"AR305"}
    # dest drift + phantom /info field; the mirrored flag, the explicit
    # dest= repair, the launcher-only annotation, and --host must not fire
    assert {f.key for f in fs} == {"tp_size", "info.legacy_knob"}


def test_ar3xx_pragma_suppresses(tmp_path):
    """Inline pragmas silence wire findings at their anchor site like any
    other rule — including the cross-file ones emitted from finalize()."""
    d = tmp_path / "fixtures"  # path keeps the registration checks scoped
    d.mkdir()
    mod = d / "wire_frag.py"
    mod.write_text(
        "def build(app, arequest_with_retry):\n"
        "    app.router.add_get('/dead', None)"
        "  # areal-lint: disable=AR301\n"
        "    # areal-lint: disable=AR301\n"
        "    return arequest_with_retry('a', '/missing')\n"
    )
    assert not [f for f in analyze_paths([str(mod)]) if f.rule == "AR301"]


def test_ar3xx_baseline_round_trip(tmp_path):
    """Baseline keys for the wire family are stable identifiers (paths,
    seam names, dests) and survive a save/load cycle; stale-entry and
    invalid-justification reporting applies to AR3xx unchanged."""
    fs = _run_fixture("ar301_routes.py")
    bl = Baseline.from_findings(fs)
    assert all(bl.covers(f) for f in fs)
    p = tmp_path / "bl.json"
    bl.save(str(p))
    bl2 = Baseline.load(str(p))
    assert all(bl2.covers(f) for f in fs)
    # stale reporting: fix the dead route -> its entry is reported unused
    remaining = [f for f in fs if f.key != "/dead_route"]
    stale = bl2.unused(remaining)
    assert [e["key"] for e in stale] == ["/dead_route"]
    # invalid(): the from_findings placeholders are flagged until justified
    assert len(bl2.invalid()) == len(bl2.entries) > 0


def test_cli_rules_family_filter_and_json():
    """`--rules AR3XX` expands to the whole family and excludes the rest;
    `--json` emits the stable schema CI and tools/lint.sh gate on."""
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "areal_tpu.analysis",
            str(FIXTURES / "ar301_routes.py"),
            str(FIXTURES / "ar201_host_sync.py"),
            "--no-baseline",
            "--rules",
            "AR3XX",
            "--json",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert r.returncode == 1
    data = json.loads(r.stdout)
    assert set(data) == {"findings", "baselined", "total", "invalid_baseline"}
    assert {f["rule"] for f in data["findings"]} == {"AR301"}
    for f in data["findings"]:
        assert set(f) == {"rule", "file", "line", "key", "message"}


# -- pragma + baseline semantics --------------------------------------------


def test_pragmas_suppress_everything():
    assert _run_fixture("pragmas_ok.py") == []


def test_baseline_covers_and_reports_stale(tmp_path):
    fs = _run_fixture("ar201_host_sync.py")
    bl = Baseline.from_findings(fs)
    assert all(bl.covers(f) for f in fs)
    # an entry whose finding disappeared is reported as stale
    bl.entries.append(
        {"file": "gone.py", "rule": "AR999", "key": "x", "justification": "j"}
    )
    stale = bl.unused(fs)
    assert len(stale) == 1 and stale[0]["file"] == "gone.py"
    # round-trips through disk
    p = tmp_path / "bl.json"
    bl.save(str(p))
    assert len(Baseline.load(str(p)).entries) == len(bl.entries)


def test_baseline_invalid_justifications_reported(tmp_path):
    """Regression (ISSUE 6 satellite): entries whose justification is
    empty, whitespace, missing, or still the `--write-baseline`
    placeholder are INVALID — they waive a rule without the review the
    justification field exists to force. Baseline.invalid() must surface
    them, and the CLI must report them through the same stderr-note
    channel as stale entries (exit code unchanged: the entry still
    suppresses its finding until someone justifies or fixes it)."""
    fs = _run_fixture("ar201_host_sync.py")
    bl = Baseline.from_findings(fs)  # placeholder justifications
    assert len(bl.invalid()) == len(bl.entries) > 0
    bl.entries[0]["justification"] = "real reason: oracle loop, sync is fine"
    bl.entries.append(
        {"file": "a.py", "rule": "AR201", "key": "k", "justification": "   "}
    )
    bl.entries.append({"file": "b.py", "rule": "AR201", "key": "k2"})
    invalid = bl.invalid()
    assert bl.entries[0] not in invalid
    assert bl.entries[-1] in invalid and bl.entries[-2] in invalid
    # CLI channel: same stderr note stream as stale entries, exit 0 when
    # every finding is covered
    p = tmp_path / "bl.json"
    bl.save(str(p))
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "areal_tpu.analysis",
            str(FIXTURES / "ar201_host_sync.py"),
            "--baseline",
            str(p),
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "invalid baseline entry" in r.stderr
    # the justified entry is not reported; the placeholder/empty ones are
    assert r.stderr.count("invalid baseline entry") == len(invalid)


def test_cli_exit_codes(tmp_path):
    bad = FIXTURES / "ar201_host_sync.py"
    env_cmd = [sys.executable, "-m", "areal_tpu.analysis"]
    r = subprocess.run(
        env_cmd + [str(bad), "--no-baseline"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert r.returncode == 1
    assert "AR201" in r.stdout
    # --write-baseline then a rerun against it exits 0
    bl = tmp_path / "bl.json"
    r = subprocess.run(
        env_cmd + [str(bad), "--baseline", str(bl), "--write-baseline"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stderr
    r = subprocess.run(
        env_cmd + [str(bad), "--baseline", str(bl)],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr


# -- repo-wide gate ----------------------------------------------------------


def test_repo_clean_against_baseline():
    """THE acceptance invariant: the whole package is clean against the
    checked-in baseline. New multi-thread writes, lock inversions, or
    hot-path hazards land here as failures with a rule code and a fix /
    annotate / baseline decision to make."""
    findings = analyze_paths([str(REPO / "areal_tpu")])
    baseline = Baseline.load(str(REPO / "tools" / "lint_baseline.json"))
    new = [f.format() for f in findings if not baseline.covers(f)]
    assert not new, "\n".join(new)


def test_repo_wire_contracts_clean_without_baseline():
    """The AR3xx family gates STRICTER than the others: real wire-contract
    violations get fixed, never baselined, so the tree must be clean for
    the family even with the baseline ignored."""
    findings = [
        f
        for f in analyze_paths([str(REPO / "areal_tpu")])
        if f.rule.startswith("AR3")
    ]
    assert not findings, "\n".join(f.format() for f in findings)
    data = json.loads((REPO / "tools" / "lint_baseline.json").read_text())
    assert not [e for e in data["entries"] if e["rule"].startswith("AR3")]


def test_baseline_entries_justified():
    data = json.loads((REPO / "tools" / "lint_baseline.json").read_text())
    for e in data["entries"]:
        assert e.get("justification", "").strip(), f"unjustified entry {e}"
        assert e["rule"] in RULES


# -- PR 3 alias-hazard regression -------------------------------------------


def test_pr3_alias_hazard_pattern_detected(tmp_path):
    """The exact bug class PR 3 found by hand: the run-ahead dispatcher
    uploaded `self._slot_lengths` via jnp.asarray (zero-copy on CPU), then
    projected the host array forward in place while the dispatched chunk
    still read the device view. The analyzer must flag the pattern; the
    shipped fix (upload through np.array) must be clean."""
    bug = tmp_path / "bug.py"
    bug.write_text(
        textwrap.dedent(
            """
            import jax.numpy as jnp
            import numpy as np

            class Sched:
                def __init__(self):
                    self._slot_lengths = np.zeros(8, np.int32)
                    self._dev_lengths = None

                def dispatch(self, active, n_chunk):
                    self._dev_lengths = jnp.asarray(self._slot_lengths)
                    self._slot_lengths[active] += n_chunk
            """
        )
    )
    fs = analyze_paths([str(bug)])
    assert any(
        f.rule == "AR203" and "self._slot_lengths" in f.key for f in fs
    ), fs

    fixed = tmp_path / "fixed.py"
    fixed.write_text(
        textwrap.dedent(
            """
            import jax.numpy as jnp
            import numpy as np

            class Sched:
                def __init__(self):
                    self._slot_lengths = np.zeros(8, np.int32)
                    self._dev_lengths = None

                def dispatch(self, active, n_chunk):
                    self._dev_lengths = jnp.asarray(np.array(self._slot_lengths))
                    self._slot_lengths[active] += n_chunk
            """
        )
    )
    assert not [f for f in analyze_paths([str(fixed)]) if f.rule == "AR203"]


def test_fixture_rule_coverage():
    """Every cataloged rule has at least one seeded fixture that triggers
    it — adding a rule without a fixture fails here."""
    all_found = set()
    for p in sorted(FIXTURES.glob("ar*.py")):
        all_found |= _codes(analyze_paths([str(p)]))
    assert all_found == set(RULES), set(RULES) - all_found
