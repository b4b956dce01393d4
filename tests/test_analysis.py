"""repro.analysis: the jaxpr-level atomics race detector & contract linter.

One known-bad function per rule (A001-A005) asserting the rule fires, a
matching known-good twin asserting it stays quiet, the PR-6 donation-bug
reconstruction caught statically, suppression mechanics, telemetry
emission, the CLI, and clean-pass sweeps over every registered entry
point.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis, atomics, telemetry
from repro.analysis import lint
from repro.analysis.entries import ENTRY_POINTS
from repro.analysis.findings import (ERROR, RULES, WARNING,
                                     _line_suppresses)
from repro.atomics import contracts
from repro.runtime.fault_tolerance import declare_donation


def _sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _rules(findings):
    return [f.rule for f in findings]


@pytest.fixture(autouse=True)
def _stream_off():
    telemetry.disable()
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# A001 — race detector
# ---------------------------------------------------------------------------

def test_a001_fires_on_raw_write_into_table():
    def bad(t, idx, v):
        tbl = atomics.AtomicTable(t)
        return tbl.data.at[idx].add(v)

    fs = analysis.check(bad, _sds((8,)), _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A001"]
    assert fs[0].severity == ERROR
    assert "atomics.execute" in fs[0].message


def test_a001_fires_on_table_passed_as_argument():
    tbl = atomics.AtomicTable(jnp.zeros((8,), jnp.int32))

    def bad(t, idx, v):
        return t.data.at[idx].set(v)

    fs = analysis.check(bad, tbl, _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A001"]


def test_a001_fires_on_aliasing_dynamic_scatter_set():
    def racy(buf, idx, v):
        return buf.at[idx].set(v)

    fs = analysis.check(racy, _sds((8,), jnp.float32), _sds((4,)),
                        _sds((4,), jnp.float32))
    assert _rules(fs) == ["A001"]


def test_a001_quiet_on_provably_unique_and_vouched_indices():
    def iota_set(buf, v):
        return buf.at[jnp.arange(4)].set(v)

    def vouched(buf, idx, v):
        return buf.at[idx].set(v, unique_indices=True)

    assert analysis.check(iota_set, _sds((8,), jnp.float32),
                          _sds((4,), jnp.float32)) == []
    assert analysis.check(vouched, _sds((8,), jnp.float32), _sds((4,)),
                          _sds((4,), jnp.float32)) == []


@pytest.mark.parametrize("where,machinery", [
    ("jax", True), ("stdlib", True), ("purelib", False),
    ("stdlib_site", False), ("repo", False)])
def test_frame_filter_keeps_installed_packages(where, machinery):
    """Frames in JAX and the standard library are dropped; an installed
    (non-editable) repro under the stdlib prefix stays user code, so the
    engine's own scatters stay sanctioned."""
    import os
    import sysconfig
    from repro.analysis import rules
    paths = sysconfig.get_paths()
    file_name = {
        "jax": jax.__file__,
        "stdlib": os.path.join(paths["stdlib"], "functools.py"),
        "purelib": os.path.join(paths["purelib"], "repro", "core",
                                "rmw.py"),
        "stdlib_site": os.path.join(paths["stdlib"], "site-packages",
                                    "repro", "core", "rmw.py"),
        "repo": rules.__file__,
    }[where]
    assert rules._is_machinery(file_name) is machinery


def test_a001_quiet_on_single_update_and_sanctioned_execute():
    def single(buf, i, v):
        return buf.at[i].set(v)

    def sanctioned(t, i, v):
        res = atomics.execute(atomics.AtomicTable(t), atomics.Faa(i, v))
        return res.table.data, res.fetched

    assert analysis.check(single, _sds((8,)), _sds(()), _sds(())) == []
    assert analysis.check(sanctioned, jnp.zeros((8,), jnp.int32),
                          _sds((4,)), _sds((4,))) == []


# ---------------------------------------------------------------------------
# A002 — primitive strength
# ---------------------------------------------------------------------------

def test_a002_fires_on_cas_expressible_as_faa():
    def cas_add(t, i, e):
        op = atomics.Cas(i, e + 1, expected=e)
        return atomics.execute(atomics.AtomicTable(t), op).table.data

    fs = analysis.check(cas_add, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A002"]
    assert fs[0].severity == WARNING
    assert "Faa" in fs[0].message
    # the message cites the consensus-number contract annotations
    assert "inf" in fs[0].message and "2" in fs[0].message


def test_a002_fires_on_cas_expressible_as_max():
    def cas_max(t, i, v, e):
        op = atomics.Cas(i, jnp.maximum(e, v), expected=e)
        return atomics.execute(atomics.AtomicTable(t), op).table.data

    fs = analysis.check(cas_max, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A002"]
    assert "Max" in fs[0].message


def test_a002_fires_on_degenerate_cas_writing_expected_back():
    def cas_noop(t, i, e):
        op = atomics.Cas(i, e, expected=e)
        return atomics.execute(atomics.AtomicTable(t), op).fetched

    fs = analysis.check(cas_noop, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A002"]


def test_a002_quiet_on_genuine_priority_cas():
    def cas_real(t, i, v, e):
        op = atomics.Cas(i, v, expected=e)
        return atomics.execute(atomics.AtomicTable(t), op).table.data

    fs = analysis.check(cas_real, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)), _sds((4,)))
    assert fs == []


# ---------------------------------------------------------------------------
# A003 — unbounded retry
# ---------------------------------------------------------------------------

def _cas_once(tab, i, v):
    res = atomics.execute(atomics.AtomicTable(tab),
                          atomics.Cas(i, v, expected=jnp.int32(0)))
    return res.table.data, jnp.all(res.success)


def test_a003_fires_on_unbounded_while_cas():
    def unbounded(t, i, v):
        def body(carry):
            tab, _ = carry
            return _cas_once(tab, i, v)

        out, _ = jax.lax.while_loop(lambda c: ~c[1], body,
                                    (t, jnp.bool_(False)))
        return out

    fs = analysis.check(unbounded, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A003"]
    assert "execute_until" in fs[0].message


def test_a003_quiet_on_round_bounded_while_cas():
    def bounded(t, i, v):
        def body(carry):
            tab, _, r = carry
            new, done = _cas_once(tab, i, v)
            return new, done, r + 1

        out, _, _ = jax.lax.while_loop(
            lambda c: ~c[1] & (c[2] < 16), body,
            (t, jnp.bool_(False), jnp.int32(0)))
        return out

    fs = analysis.check(bounded, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)))
    assert fs == []


def test_a003_quiet_on_cas_free_while():
    def loop(x):
        return jax.lax.while_loop(lambda c: jnp.any(c > 0),
                                  lambda c: c - 1, x)

    assert analysis.check(loop, _sds((4,))) == []


# ---------------------------------------------------------------------------
# A004 — donation safety
# ---------------------------------------------------------------------------

def test_a004_fires_on_donated_buffer_read_after_call():
    consume = jax.jit(lambda x: x * 2, donate_argnums=(0,))

    def bad(x):
        y = consume(x)
        return y + x                  # x is read AFTER being donated

    fs = analysis.check(bad, _sds((8,), jnp.float32))
    assert "A004" in _rules(fs)


def test_a004_quiet_when_donated_buffer_unused_afterwards():
    consume = jax.jit(lambda x: x * 2, donate_argnums=(0,))

    def ok(x):
        return consume(x) * 3

    assert analysis.check(ok, _sds((8,), jnp.float32)) == []


def test_a004_check_recovery_reconstructs_pr6_donation_bug():
    # the PR-6 bug class: a donating jitted step handed to recovery with a
    # CAPTURED state value — after step 0 the captured buffers are donated
    # away and every scratch restart replays aliased garbage
    step = declare_donation(
        jax.jit(lambda s, st: st * 2, donate_argnums=(1,)), (1,))
    fs = analysis.check_recovery(step, jnp.zeros((4,)))
    assert _rules(fs) == ["A004"]
    assert fs[0].severity == ERROR
    assert "factory" in fs[0].message


def test_a004_check_recovery_quiet_with_state_factory():
    step = declare_donation(
        jax.jit(lambda s, st: st * 2, donate_argnums=(1,)), (1,))
    assert analysis.check_recovery(step, lambda: jnp.zeros((4,))) == []


def test_a004_check_recovery_introspects_jit_without_declaration():
    # no declare_donation wrapper: donation is discovered from the jitted
    # function's own trace metadata when example args are provided
    step = jax.jit(lambda s, st: st * 2, donate_argnums=(1,))
    fs = analysis.check_recovery(step, jnp.zeros((4,)),
                                 example_args=(0, jnp.zeros((4,))))
    assert _rules(fs) == ["A004"]


def test_declare_donation_preserves_call_and_metadata():
    f = declare_donation(lambda s, st: st + s, 1)
    assert f.donate_argnums == (1,)
    assert f(2, 3) == 5


def test_run_with_recovery_warns_on_donating_step_with_captured_state():
    from repro.runtime.fault_tolerance import (FaultConfig,
                                               run_with_recovery)

    step = declare_donation(lambda s, st: st + 1, (1,))
    with telemetry.capture() as events:
        res = run_with_recovery(step, 0, 3, FaultConfig(), lambda s, x: None,
                                lambda: None)
    assert res.steps_done == 3
    hazards = [e for e in events.events
               if e["event"] == "recovery.donation_hazard"]
    assert len(hazards) == 1
    # the hazard is a static property of the call, not a recovery
    # occurrence: the run-local event trace must not change shape
    assert "recovery.donation_hazard" not in res.event_counts()


# ---------------------------------------------------------------------------
# A005 — shard contract
# ---------------------------------------------------------------------------

def test_a005_fires_on_sharded_execute_outside_shard_map():
    def outside(t, i, v):
        tbl = atomics.AtomicTable(t, axis="dev")
        return atomics.execute(tbl, atomics.Faa(i, v)).table.data

    fs = analysis.check(outside, jnp.zeros((8,), jnp.int32),
                        _sds((4,)), _sds((4,)))
    assert _rules(fs) == ["A005"]
    assert "shard_map" in fs[0].message


def _shard_mapped(body):
    from jax.sharding import PartitionSpec as P

    from repro.sharding import make_mesh, shard_map_compat

    mesh = make_mesh((1,), ("dev",))
    spec = P("dev")
    return shard_map_compat(body, mesh, (spec, spec, spec), (spec,))


def test_a005_quiet_inside_shard_map():
    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis="dev")
        return (atomics.execute(tbl, atomics.Faa(i[0], v[0])).table.data,)

    fs = analysis.check(_shard_mapped(fn), _sds((8,)), _sds((1, 4)),
                        _sds((1, 4)))
    assert fs == []


def test_a005_fires_on_reverse_ranks_without_forward_fetch():
    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis="dev")
        r1 = atomics.execute(tbl, atomics.Swp(i[0], v[0]),
                             need_fetched=False)
        r2 = atomics.execute(r1.table, atomics.Swp(i[0], v[0]),
                             reverse_ranks=True, need_fetched=False)
        return (r2.table.data,)

    fs = analysis.check(_shard_mapped(fn), _sds((8,)), _sds((1, 4)),
                        _sds((1, 4)))
    assert _rules(fs) == ["A005"]
    assert "reverse_ranks" in fs[0].message


def test_a005_quiet_on_swp_plus_revert_with_forward_fetch():
    # the sanctioned SWP+revert scheme (core/bfs.py): forward pass fetches
    # pre-images, reversed pass writes them back
    def fn(t, i, v):
        tbl = atomics.AtomicTable(t, axis="dev")
        r1 = atomics.execute(tbl, atomics.Swp(i[0], v[0]),
                             need_fetched=True)
        r2 = atomics.execute(r1.table, atomics.Swp(i[0], r1.fetched),
                             reverse_ranks=True, need_fetched=False)
        return (r2.table.data,)

    fs = analysis.check(_shard_mapped(fn), _sds((8,)), _sds((1, 4)),
                        _sds((1, 4)))
    assert fs == []


# ---------------------------------------------------------------------------
# suppression mechanics
# ---------------------------------------------------------------------------

def test_line_suppression_parser():
    assert _line_suppresses("x = 1  # atomics-lint: disable=A001", "A001")
    assert _line_suppresses("# atomics-lint: disable=A001,A003", "A003")
    assert _line_suppresses("# atomics-lint: disable=all", "A005")
    assert not _line_suppresses("# atomics-lint: disable=A001", "A002")
    assert not _line_suppresses("# just a comment", "A001")


def test_suppressed_findings_stay_visible_but_do_not_gate(tmp_path):
    mod = tmp_path / "bad_mod.py"
    mod.write_text(
        "import jax.numpy as jnp\n"
        "def racy(buf, idx, v):\n"
        "    # atomics-lint: disable=A001\n"
        "    return buf.at[idx].set(v)\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location("bad_mod", mod)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    fs = analysis.check(m.racy, _sds((8,), jnp.float32), _sds((4,)),
                        _sds((4,), jnp.float32))
    assert _rules(fs) == ["A001"]
    assert fs[0].suppressed
    # suppressed errors do not fail the sweep gate
    assert all(f.suppressed for f in fs if f.severity == ERROR)


def test_repo_suppressions_are_commented():
    # every in-repo suppression must carry a why (the comment block above
    # it) — spot-check the one deliberate suppression shipped today
    from pathlib import Path
    src = Path(__file__).resolve().parents[1] / "src/repro/models/moe.py"
    lines = src.read_text().splitlines()
    marks = [i for i, ln in enumerate(lines) if "atomics-lint:" in ln]
    assert marks, "expected the moe dispatch suppression to exist"
    for i in marks:
        context = "\n".join(lines[max(0, i - 4):i])
        assert "scratch row" in context or "distinct" in context


# ---------------------------------------------------------------------------
# telemetry + reporting
# ---------------------------------------------------------------------------

def test_findings_emit_telemetry_events():
    def bad(t, idx, v):
        tbl = atomics.AtomicTable(t)
        return tbl.data.at[idx].add(v)

    with telemetry.capture() as events:
        analysis.check(bad, _sds((8,)), _sds((4,)), _sds((4,)),
                       entry="unit.bad")
    evs = [e for e in events.events if e["event"] == "analysis.finding"]
    assert len(evs) == 1
    assert evs[0]["rule"] == "A001"
    assert evs[0]["severity"] == ERROR
    assert evs[0]["entry"] == "unit.bad"
    assert evs[0]["suppressed"] is False


def test_report_renders_analysis_section():
    from repro.telemetry.report import build_report, render_text

    events = [{"event": "analysis.finding", "rule": "A001",
               "severity": "error", "file": "x.py", "line": 3,
               "entry": "e", "suppressed": False, "message": "m"}]
    rep = build_report(events, fit=False)
    assert rep["analysis"][0]["rule"] == "A001"
    text = render_text(rep)
    assert "static analysis" in text
    assert "x.py:3" in text and "A001" in text


# ---------------------------------------------------------------------------
# CLI + sweep + fixture
# ---------------------------------------------------------------------------

def test_cli_list_and_single_entry(capsys):
    assert lint.main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in ENTRY_POINTS:
        assert name in out
    assert lint.main(["--entries", "bfs.local"]) == 0
    out = capsys.readouterr().out
    assert "[bfs.local] clean" in out


def test_cli_json_output(capsys):
    assert lint.main(["--entries", "bfs.local", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"]["errors"] == 0
    assert isinstance(payload["findings"], list)


def test_cli_unknown_entry_is_an_error(capsys):
    assert lint.main(["--entries", "no.such.entry"]) == 1
    assert "A000" in capsys.readouterr().out


def test_sweep_crashing_entry_becomes_a000_finding(monkeypatch):
    from repro.analysis import entries as entries_mod

    def boom():
        raise RuntimeError("entry exploded")

    monkeypatch.setitem(entries_mod.ENTRY_POINTS, "unit.boom", boom)
    res = lint.sweep(["unit.boom"])
    fs = res["unit.boom"]
    assert _rules(fs) == ["A000"]
    assert "entry exploded" in fs[0].message


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_registered_entry_points_pass_clean(entry):
    findings = ENTRY_POINTS[entry]()
    bad = [f for f in findings if f.severity == ERROR and not f.suppressed]
    assert bad == [], "\n".join(f.format() for f in bad)


def test_atomics_lint_fixture_gates_and_returns(atomics_lint):
    def ok(buf, v):
        return buf.at[jnp.arange(4)].set(v)

    assert atomics_lint(ok, _sds((8,), jnp.float32),
                        _sds((4,), jnp.float32)) == []

    def bad(t, idx, v):
        tbl = atomics.AtomicTable(t)
        return tbl.data.at[idx].add(v)

    with pytest.raises(pytest.fail.Exception):
        atomics_lint(bad, _sds((8,)), _sds((4,)), _sds((4,)))


# ---------------------------------------------------------------------------
# analysis must not perturb production behavior
# ---------------------------------------------------------------------------

def test_no_marker_leaks_outside_observation():
    def fn(t, i, v):
        res = atomics.execute(atomics.AtomicTable(t), atomics.Faa(i, v))
        return res.table.data

    analysis.check(fn, jnp.zeros((8,), jnp.int32), _sds((4,)), _sds((4,)))
    assert not contracts.active()
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((8,), jnp.int32),
                               jnp.zeros((4,), jnp.int32),
                               jnp.zeros((4,), jnp.int32))
    assert contracts.MARKER not in str(jaxpr)


def test_checked_function_still_executes_correctly():
    def fn(t, i, v):
        res = atomics.execute(atomics.AtomicTable(t), atomics.Faa(i, v))
        return res.table.data

    t = jnp.zeros((8,), jnp.int32)
    i = jnp.array([1, 1, 2, 7], jnp.int32)
    v = jnp.array([1, 2, 3, 4], jnp.int32)
    before = np.asarray(fn(t, i, v))
    analysis.check(fn, t, i, v)
    after = np.asarray(fn(t, i, v))
    np.testing.assert_array_equal(before, after)
    np.testing.assert_array_equal(
        after, np.asarray([0, 3, 3, 0, 0, 0, 0, 4]))


def test_rule_table_is_complete():
    assert set(RULES) == {"A000", "A001", "A002", "A003", "A004", "A005"}
    for rule, (sev, desc) in RULES.items():
        assert sev in (ERROR, WARNING)
        assert desc
