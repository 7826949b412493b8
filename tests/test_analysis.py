"""replint (repro.analysis): per-rule fixtures + framework behaviour.

Each rule gets a bad fixture it MUST fire on and a good twin it MUST stay
silent on; the bad fixtures double as the CLI exit-code matrix (ISSUE 7
acceptance: non-zero on each rule's fixture).  Two regression fixtures
reproduce real past defects: the PR 4 salted-``hash()`` Maglev table build
(RPL003) and the acl_match wrapper that swallowed ``interpret`` (RPL006).
The RPL002 test injects a counter into a fake engine module and asserts
the parity rule demands the loop mirror.
"""
from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import ALL_RULES, analyze, load_baseline, load_project
from repro.analysis.baseline import render_baseline
from repro.analysis.cli import main
from repro.analysis.rules import rule_by_id

REPO = Path(__file__).resolve().parents[1]


def write_tree(root: Path, files: dict[str, str]) -> None:
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))


def run_replint(tmp_path: Path, files: dict[str, str], rule_id=None):
    write_tree(tmp_path, files)
    rules = [rule_by_id(rule_id)] if rule_id else ALL_RULES
    return analyze(load_project([tmp_path], root=tmp_path), rules)


def fired(findings) -> set[str]:
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# RPL001 — dispatch discipline
# ---------------------------------------------------------------------------

RPL001_BAD = {"nf/fw.py": """\
    from repro.backend.ref import acl_match

    def route(ips, rules):
        return acl_match(ips, rules)
    """}

RPL001_GOOD = {"nf/fw.py": """\
    from repro.backend.registry import dispatch
    from repro.core.header import crc16_tag

    def route(ips, rules, backend):
        return dispatch("acl_match", backend)(ips, rules)

    def tag(ti, clk, backend):
        return crc16_tag(ti, clk, backend=backend)
    """}


def test_rpl001_fires_on_primitive_import_and_call(tmp_path):
    findings = run_replint(tmp_path, RPL001_BAD, "RPL001")
    assert len(findings) == 2  # the import and the call
    assert all(f.rule == "RPL001" for f in findings)
    assert all(f.path == "nf/fw.py" for f in findings)


def test_rpl001_silent_on_dispatch_and_backend_kwarg(tmp_path):
    assert run_replint(tmp_path, RPL001_GOOD, "RPL001") == []


def test_rpl001_exempts_backend_and_kernels_and_tests(tmp_path):
    files = {
        "backend/registry.py": "from repro.backend.ref import acl_match\n",
        "kernels/acl/ref.py": "from repro.backend.ref import acl_match\n",
        "tests/test_kernels.py": "from repro.backend.ref import acl_match\n",
    }
    assert run_replint(tmp_path, files, "RPL001") == []


# ---------------------------------------------------------------------------
# RPL002 — engine≡loop structural parity
# ---------------------------------------------------------------------------

def _parity_tree(engine_extra: str = "", loop_extra: str = ""):
    return {
        "switchsim/engine.py": f"""\
            from repro.core import counters as C

            def run(state):
                state = C.bump(state, "fault_drops", 1)
            {engine_extra}
                ys = dict(wire_pkts=1, wire_bytes=2)
                return ys
            """,
        "switchsim/simulate.py": f"""\
            from repro.core.counters import bump

            def simulate_loop(state, tel):
                state = bump(state, "fault_drops", 1)
            {loop_extra}
                tel["wire_pkts"] += 1
                tel["wire_bytes"] += 1
                return tel
            """,
        "switchsim/telemetry.py": """\
            import dataclasses

            @dataclasses.dataclass(frozen=True)
            class LinkTelemetry:
                wire_pkts: int = 0
                wire_bytes: int = 0
            """,
    }


def test_rpl002_flags_counter_injected_only_into_engine(tmp_path):
    """The satellite case: add a counter to the (fake) engine without the
    loop mirror — parity must fail lint, naming the counter."""
    bump = '    state = C.bump(state, "injected_counter", 1)'
    findings = run_replint(tmp_path, _parity_tree(engine_extra=bump),
                           "RPL002")
    assert len(findings) == 1
    f = findings[0]
    assert "injected_counter" in f.message and f.path == "switchsim/engine.py"


def test_rpl002_flags_counter_only_in_loop(tmp_path):
    bump = '    state = bump(state, "loop_only", 1)'
    findings = run_replint(tmp_path, _parity_tree(loop_extra=bump), "RPL002")
    assert len(findings) == 1
    assert "loop_only" in findings[0].message
    assert findings[0].path == "switchsim/simulate.py"


def test_rpl002_flags_unmirrored_telemetry_field(tmp_path):
    tree = _parity_tree()
    tree["switchsim/telemetry.py"] = textwrap.dedent("""\
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class LinkTelemetry:
            wire_pkts: int = 0
            wire_bytes: int = 0
            recirc_pkts: int = 0
        """)
    findings = run_replint(tmp_path, tree, "RPL002")
    # neither side surfaces recirc_pkts: one finding per side
    assert len(findings) == 2
    assert all("recirc_pkts" in f.message for f in findings)


def test_rpl002_silent_when_mirrored(tmp_path):
    assert run_replint(tmp_path, _parity_tree(), "RPL002") == []


def test_rpl002_real_tree_is_parity_clean():
    project = load_project([REPO / "src" / "repro" / "switchsim"], root=REPO)
    assert analyze(project, [rule_by_id("RPL002")]) == []


# ---------------------------------------------------------------------------
# RPL003 — nondeterminism ban (the PR 4 salted-hash() Maglev class)
# ---------------------------------------------------------------------------

MAGLEV_PR4_BUG = {"nf/maglev.py": """\
    def build_table(backends, size=64):
        # the PR 4 defect: builtin hash() of a str is PYTHONHASHSEED-salted,
        # so each process builds a different permutation table
        table = [-1] * size
        for i, name in enumerate(backends):
            offset = hash(name) % size
            skip = hash(name + "skip") % (size - 1) + 1
            table[(offset + i * skip) % size] = i
        return table
    """}

MAGLEV_FIXED = {"nf/maglev.py": """\
    def _mix64(x):
        x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9 & (2**64 - 1)
        x = (x ^ (x >> 27)) * 0x94D049BB133111EB & (2**64 - 1)
        return x ^ (x >> 31)

    def build_table(backends, size=64):
        table = [-1] * size
        for i, _ in enumerate(backends):
            offset = _mix64(i) % size
            skip = _mix64(i * 2 + 1) % (size - 1) + 1
            table[(offset + i * skip) % size] = i
        return table
    """}


def test_rpl003_catches_the_pr4_maglev_hash_bug(tmp_path):
    findings = run_replint(tmp_path, MAGLEV_PR4_BUG, "RPL003")
    assert len(findings) == 2  # both salted hash() calls
    assert all("hash()" in f.message for f in findings)


def test_rpl003_silent_on_splitmix_fix(tmp_path):
    assert run_replint(tmp_path, MAGLEV_FIXED, "RPL003") == []


def test_rpl003_flags_wallclock_and_set_iteration(tmp_path):
    files = {"core/build.py": """\
        import time

        def stamp():
            return time.time()

        def order(names):
            out = []
            for n in set(names):
                out.append(n)
            return out
        """}
    findings = run_replint(tmp_path, files, "RPL003")
    assert len(findings) == 2
    msgs = " ".join(f.message for f in findings)
    assert "time.time" in msgs and "iterating a set" in msgs


def test_rpl003_silent_on_sorted_set(tmp_path):
    files = {"core/build.py": """\
        def order(names):
            return [n for n in sorted(set(names))]
        """}
    assert run_replint(tmp_path, files, "RPL003") == []


# ---------------------------------------------------------------------------
# RPL004 — recompile hazards
# ---------------------------------------------------------------------------

def test_rpl004_flags_nonfrozen_config(tmp_path):
    files = {"serving/engine.py": """\
        import dataclasses

        @dataclasses.dataclass
        class EngineConfig:
            max_batch: int = 8
        """}
    findings = run_replint(tmp_path, files, "RPL004")
    assert len(findings) == 1 and "EngineConfig" in findings[0].message


def test_rpl004_silent_on_frozen_config_and_result_types(tmp_path):
    files = {"serving/engine.py": """\
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class EngineConfig:
            max_batch: int = 8

        @dataclasses.dataclass
        class EngineResult:
            merged: list = None
        """}
    assert run_replint(tmp_path, files, "RPL004") == []


def test_rpl004_flags_shape_fstring_only_under_trace(tmp_path):
    files = {"core/shapes.py": """\
        import jax

        @jax.jit
        def traced(x):
            label = f"in={x.shape}"
            return x

        def host(x):
            return f"in={x.shape}"
        """}
    findings = run_replint(tmp_path, files, "RPL004")
    assert len(findings) == 1
    assert "trace time" in findings[0].message


# ---------------------------------------------------------------------------
# RPL005 — host sync in hot paths
# ---------------------------------------------------------------------------

def test_rpl005_flags_syncs_in_traced_functions(tmp_path):
    files = {"switchsim/hot.py": """\
        from functools import partial

        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def tally(x):
            return float(jnp.sum(x))

        def step(c, x):
            n = jnp.sum(x).item()
            return c + n, n

        def drive(xs):
            return jax.lax.scan(step, 0, xs)

        def body(x):
            return np.asarray(x)

        run = partial(jax.jit, static_argnames=("k",))(body)
        """}
    findings = run_replint(tmp_path, files, "RPL005")
    assert len(findings) == 3
    msgs = " ".join(f.message for f in findings)
    assert "float()" in msgs and ".item()" in msgs and "np.asarray" in msgs


def test_rpl005_silent_on_host_side_finalize(tmp_path):
    files = {"switchsim/hot.py": """\
        import jax.numpy as jnp
        import numpy as np

        def finalize(ys):
            return int(np.asarray(ys["occ"]).max())

        def cast_config(cfg):
            return int(cfg.pipes)
        """}
    assert run_replint(tmp_path, files, "RPL005") == []


def test_rpl005_scoped_to_hot_dirs(tmp_path):
    files = {"launch/report.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return float(jnp.sum(x))
        """}
    assert run_replint(tmp_path, files, "RPL005") == []


@pytest.mark.parametrize("wrap", [
    "run = jax.shard_map(body, mesh=None, in_specs=None, out_specs=None)",
    "run = partial(jax.shard_map, mesh=None, in_specs=None,\n"
    "              out_specs=None)(body)",
])
def test_rpl005_fires_inside_shard_map_body_under_distributed(tmp_path,
                                                              wrap):
    # the fabric-sharding scope extension (DESIGN.md §12): shard_map bodies
    # are traced code, and distributed/ is a hot dir now
    files = {"distributed/fab.py": textwrap.dedent("""\
        from functools import partial

        import jax
        import jax.numpy as jnp
        import numpy as np

        def body(x):
            return np.asarray(jnp.sum(x))

        """) + wrap + "\n"}
    findings = run_replint(tmp_path, files, "RPL005")
    assert len(findings) == 1 and "np.asarray" in findings[0].message


def test_rpl005_silent_on_host_side_code_under_distributed(tmp_path):
    files = {"distributed/fab.py": """\
        import numpy as np

        def resolve_devices(pipes, devices):
            return int(np.gcd(pipes, devices))
        """}
    assert run_replint(tmp_path, files, "RPL005") == []


def test_rpl005_real_hot_paths_are_clean():
    project = load_project(
        [REPO / "src" / "repro" / "switchsim",
         REPO / "src" / "repro" / "backend",
         REPO / "src" / "repro" / "distributed"], root=REPO)
    assert analyze(project, [rule_by_id("RPL005")]) == []


# ---------------------------------------------------------------------------
# RPL006 — kernel hygiene (the acl_match interpret-swallow regression)
# ---------------------------------------------------------------------------

def _kernel_pkg(ops_body: str, kernel_sig: str = "x, *, interpret=True"):
    return {
        "kernels/foo/kernel.py": f"""\
            def foo_kernel({kernel_sig}):
                return x
            """,
        "kernels/foo/ref.py": """\
            def foo_ref(x):
                return x
            """,
        "kernels/foo/ops.py": ops_body,
    }


def test_rpl006_catches_dropped_interpret_forward(tmp_path):
    """Regression shape: kernels/acl_match/ops.py took ``interpret`` but
    never passed it on, so backend="pallas" silently ran interpret mode."""
    files = _kernel_pkg("""\
        from repro.kernels.foo.kernel import foo_kernel

        def foo(x, interpret: bool = True):
            return foo_kernel(x)
        """)
    findings = run_replint(tmp_path, files, "RPL006")
    assert len(findings) == 1
    assert "does not forward interpret" in findings[0].message


def test_rpl006_silent_when_interpret_forwarded(tmp_path):
    files = _kernel_pkg("""\
        from repro.kernels.foo.kernel import foo_kernel

        def foo(x, interpret: bool = True):
            return foo_kernel(x, interpret=interpret)
        """)
    assert run_replint(tmp_path, files, "RPL006") == []


def test_rpl006_flags_wrapper_without_interpret_kwarg(tmp_path):
    files = _kernel_pkg("""\
        from repro.kernels.foo.kernel import foo_kernel

        def foo(x):
            return foo_kernel(x, interpret=True)
        """)
    findings = run_replint(tmp_path, files, "RPL006")
    assert len(findings) == 1 and "no interpret kwarg" in findings[0].message


def test_rpl006_flags_signature_mismatch_with_ref(tmp_path):
    files = _kernel_pkg("""\
        from repro.kernels.foo.kernel import foo_kernel

        def foo(x, extra_arg, interpret: bool = True):
            return foo_kernel(x, interpret=interpret)
        """)
    findings = run_replint(tmp_path, files, "RPL006")
    assert len(findings) == 1 and "signature" in findings[0].message


def test_rpl006_flags_kernel_without_interpret_path(tmp_path):
    files = _kernel_pkg("""\
        from repro.kernels.foo.kernel import foo_kernel

        def foo(x, interpret: bool = True):
            return foo_kernel(x, interpret=interpret)
        """, kernel_sig="x")
    findings = run_replint(tmp_path, files, "RPL006")
    assert any("no interpret parameter" in f.message for f in findings)


def test_rpl006_real_kernel_packages_are_clean():
    project = load_project([REPO / "src" / "repro" / "kernels"], root=REPO)
    assert analyze(project, [rule_by_id("RPL006")]) == []


# ---------------------------------------------------------------------------
# RPL007 — oracle-test discipline
# ---------------------------------------------------------------------------

RPL007_BAD = {"tests/test_engine.py": """\
    import numpy as np

    def test_engine_matches_loop_bitexact():
        a, b = [1, 2], [1, 2]
        assert np.allclose(a, b)
    """}


def test_rpl007_fires_on_approx_assert_in_exactness_test(tmp_path):
    findings = run_replint(tmp_path, RPL007_BAD, "RPL007")
    assert len(findings) == 1 and "allclose" in findings[0].message


def test_rpl007_silent_on_exact_assert_and_nonexactness_tests(tmp_path):
    files = {"tests/test_engine.py": """\
        import numpy as np

        def test_engine_matches_loop_bitexact():
            assert np.array_equal([1], [1])

        def test_attention_kernel_close_enough():
            # not an exactness oracle: approx compare is fine here
            assert np.allclose([1.0], [1.0 + 1e-9])
        """}
    assert run_replint(tmp_path, files, "RPL007") == []


def test_rpl007_flags_tolerance_kwargs(tmp_path):
    files = {"tests/test_backend.py": """\
        import numpy.testing as npt

        class TestCrossBackendParity:
            def test_backends_match(self):
                npt.assert_array_almost_equal([1.0], [1.0], rtol=1e-6)
        """}
    findings = run_replint(tmp_path, files, "RPL007")
    assert len(findings) == 1 and "rtol=" in findings[0].message


# ---------------------------------------------------------------------------
# CLI + baseline behaviour
# ---------------------------------------------------------------------------

ALL_BAD = {
    "RPL001": RPL001_BAD,
    "RPL002": _parity_tree(
        engine_extra='    state = C.bump(state, "injected", 1)'),
    "RPL003": MAGLEV_PR4_BUG,
    "RPL004": {"core/cfg.py": ("import dataclasses\n\n"
                               "@dataclasses.dataclass\n"
                               "class FooConfig:\n    n: int = 1\n")},
    "RPL005": {"switchsim/hot.py": ("import jax\nimport jax.numpy as jnp\n\n"
                                    "@jax.jit\ndef f(x):\n"
                                    "    return float(jnp.sum(x))\n")},
    "RPL006": _kernel_pkg("""\
        from repro.kernels.foo.kernel import foo_kernel

        def foo(x, interpret: bool = True):
            return foo_kernel(x)
        """),
    "RPL007": RPL007_BAD,
}


@pytest.mark.parametrize("rule_id", sorted(ALL_BAD))
def test_cli_exits_nonzero_on_each_rule_fixture(tmp_path, capsys, rule_id):
    write_tree(tmp_path, ALL_BAD[rule_id])
    rc = main([str(tmp_path), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert rule_id in out


def test_cli_exit_zero_on_clean_tree_and_json_report(tmp_path, capsys):
    write_tree(tmp_path, RPL001_GOOD)
    report = tmp_path / "replint.json"
    rc = main([str(tmp_path), "--no-baseline", "--json", str(report)])
    assert rc == 0
    data = json.loads(report.read_text())
    assert data["findings"] == [] and data["files_analyzed"] == 1


def test_baseline_suppresses_then_goes_stale(tmp_path, capsys):
    write_tree(tmp_path, RPL001_BAD)
    # same root the CLI will use (cwd), so fingerprint paths line up
    findings = analyze(load_project([tmp_path]), ALL_RULES)
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"suppressions": [
        {"fingerprint": f.fingerprint, "rule": f.rule, "path": f.path,
         "justification": "fixture exemption"} for f in findings]}))
    assert main([str(tmp_path), "--baseline", str(bl)]) == 0
    # fix the violation: every matching entry must now fail as stale
    (tmp_path / "nf" / "fw.py").write_text(
        textwrap.dedent(RPL001_GOOD["nf/fw.py"]))
    rc = main([str(tmp_path), "--baseline", str(bl)])
    out = capsys.readouterr().out
    assert rc == 1 and "STALE" in out


def test_baseline_rejects_empty_justification(tmp_path):
    write_tree(tmp_path, RPL001_BAD)
    findings = analyze(load_project([tmp_path], root=tmp_path), ALL_RULES)
    bl = tmp_path / "bl.json"
    bl.write_text(render_baseline(findings))  # skeleton: justifications empty
    with pytest.raises(ValueError, match="justification"):
        load_baseline(bl)


def test_fingerprints_survive_line_drift_not_content_change(tmp_path):
    write_tree(tmp_path, RPL001_BAD)
    before = analyze(load_project([tmp_path], root=tmp_path), ALL_RULES)
    src = (tmp_path / "nf" / "fw.py").read_text()
    (tmp_path / "nf" / "fw.py").write_text("# a leading comment\n" + src)
    after = analyze(load_project([tmp_path], root=tmp_path), ALL_RULES)
    assert {f.fingerprint for f in before} == {f.fingerprint for f in after}
    assert [f.line for f in before] != [f.line for f in after]


def test_repo_tree_is_clean_under_committed_baseline(monkeypatch):
    """The acceptance criterion, as a test: the shipped tree + shipped
    baseline lint clean."""
    monkeypatch.chdir(REPO)
    assert main(["src", "tests", "--baseline",
                 str(REPO / "replint_baseline.json")]) == 0
