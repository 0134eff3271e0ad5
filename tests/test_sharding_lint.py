"""Sharding & collective consistency tier tests (SH01-SH04, NM01) plus
the runtime ShardGuard, the ``--diff`` incremental mode, and the
hostile-input (skip-don't-crash) contract.

Same contract as test_graftlint.py / test_concurrency_lint.py: every
rule is demonstrated on a known-bad fixture AND shown quiet on the
corresponding known-good rewrite, the pragma / baseline plumbing
round-trips, and the gauges publish.  The seeded regression test at the
bottom plants a wrong-axis collective and a mismatched NamedSharding and
shows the static tier catches both, then dispatches through a ShardGuard
wrap and shows the runtime half counts the same resharding live.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from deeplearning4j_tpu.analysis import (
    ACTIVE,
    BASELINED,
    SUPPRESSED,
    Analyzer,
    Baseline,
    active,
    all_rules,
    emit_metrics,
)
from deeplearning4j_tpu.analysis.sharding import (
    axis_registry,
    set_axis_registry,
    sharding_info,
)
from deeplearning4j_tpu.analysis.shardguard import (
    SHARDGUARD,
    ShardGuard,
    shardguard_active,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def lint(source, only=None, baseline=None, path="snippet.py"):
    rules = [all_rules()[only]] if only else None
    analyzer = Analyzer(rules=rules, baseline=baseline)
    findings = analyzer.analyze_source(textwrap.dedent(source), path)
    assert not analyzer.errors
    return findings


def rules_hit(findings):
    return {f.rule for f in findings if f.status == ACTIVE}


@pytest.fixture(autouse=True)
def _restore_axis_registry():
    yield
    set_axis_registry(None)


# ------------------------------------------------------------------- SH01

SH01_BAD = """
    import jax
    import numpy as np
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def step(x):
        return lax.psum(x, "tp")   # axis the mesh never binds

    stepped = shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"))
"""


def test_sh01_fires_on_unbound_axis():
    findings = active(lint(SH01_BAD, only="SH01"))
    assert len(findings) == 1
    assert "psum" in findings[0].message
    assert "'tp'" in findings[0].message


def test_sh01_quiet_when_axis_bound():
    src = SH01_BAD.replace('lax.psum(x, "tp")', 'lax.psum(x, "dp")')
    assert active(lint(src, only="SH01")) == []


def test_sh01_quiet_when_mesh_unresolvable():
    # the mesh arrives as a parameter: binding is unknown, not wrong
    src = """
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        def build(mesh):
            def step(x):
                return lax.psum(x, "tp")
            return shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P("dp"))
    """
    assert active(lint(src, only="SH01")) == []


def test_sh01_quiet_when_never_wrapped():
    # a collectives-wrapper module: axis comes in as a parameter, the
    # function is never visibly shard_map'ed — confidence says silent
    src = """
        from jax import lax

        def psum_helper(x, axis):
            return lax.psum(x, axis)
    """
    assert active(lint(src, only="SH01")) == []


def test_sh01_interprocedural_propagation():
    # the collective lives in a helper CALLED from the wrapped step
    src = """
        import jax
        import numpy as np
        from jax import lax, shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("dp",))

        def reduce_wrong(x):
            return lax.pmean(x, "tp")

        def step(x):
            return reduce_wrong(x)

        stepped = shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                            out_specs=P("dp"))
    """
    findings = active(lint(src, only="SH01"))
    assert len(findings) == 1
    assert "pmean" in findings[0].message


def test_sh01_pmap_axis_name_binds():
    src = """
        import jax
        from jax import lax

        def step(x):
            return lax.psum(x, "tp")

        stepped = jax.pmap(step, axis_name="dp")
    """
    findings = active(lint(src, only="SH01"))
    assert len(findings) == 1
    good = src.replace('axis_name="dp"', 'axis_name="tp"')
    assert active(lint(good, only="SH01")) == []


def test_sh01_mesh_helper_calls_bind_registry_axes():
    # make_mesh() binds the whole registry; local_mesh binds dp only
    src = """
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P
        from deeplearning4j_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()

        def step(x):
            return lax.psum(x, "tp")

        stepped = shard_map(step, mesh=mesh, in_specs=(P("tp"),),
                            out_specs=P("tp"))
    """
    assert active(lint(src, only="SH01")) == []


# ------------------------------------------------------------------- SH02

SH02_BAD = """
    from jax.sharding import PartitionSpec as P

    spec = P("dpx", None)   # typo'd axis name
"""


def test_sh02_fires_on_unknown_axis_name():
    findings = active(lint(SH02_BAD, only="SH02"))
    assert len(findings) == 1
    assert "'dpx'" in findings[0].message
    assert "canonical axis registry" in findings[0].message


def test_sh02_quiet_on_registry_axis():
    src = SH02_BAD.replace('"dpx"', '"dp"')
    assert active(lint(src, only="SH02")) == []


def test_sh02_checks_multi_axis_dim_tuples():
    src = """
        from jax.sharding import PartitionSpec as P

        spec = P(("dp", "tpx"), None)
    """
    findings = active(lint(src, only="SH02"))
    assert len(findings) == 1
    assert "'tpx'" in findings[0].message


def test_sh02_registry_hook():
    set_axis_registry(("rows", "cols"))
    assert axis_registry() == frozenset({"rows", "cols"})
    src = """
        from jax.sharding import PartitionSpec as P

        spec = P("rows")
        bad = P("dp")
    """
    findings = active(lint(src, only="SH02"))
    assert len(findings) == 1
    assert "'dp'" in findings[0].message
    set_axis_registry(None)
    assert "dp" in axis_registry()


def test_sh02_registry_parsed_from_mesh_module():
    # the linter's ground truth IS parallel/mesh.py — never disagree
    from deeplearning4j_tpu.parallel import mesh as mesh_mod

    assert axis_registry() == frozenset(mesh_mod.AXES)


# ------------------------------------------------------------------- SH03

SH03_IN_BAD = """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def step(x, y):
        return x + y

    stepped = shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"))
"""


def test_sh03_fires_on_in_specs_arity_mismatch():
    findings = active(lint(SH03_IN_BAD, only="SH03"))
    assert len(findings) == 1
    assert "in_specs has 1" in findings[0].message
    assert "`step`" in findings[0].message


def test_sh03_quiet_when_in_specs_match():
    src = SH03_IN_BAD.replace('in_specs=(P("dp"),)',
                              'in_specs=(P("dp"), P("dp"))')
    assert active(lint(src, only="SH03")) == []


def test_sh03_defaults_widen_the_accepted_range():
    src = """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def step(x, y, scale=1.0):
            return x + y * scale

        stepped = shard_map(step, mesh=mesh, in_specs=(P("dp"), P("dp")),
                            out_specs=P("dp"))
    """
    assert active(lint(src, only="SH03")) == []


def test_sh03_fires_on_out_specs_arity_mismatch():
    src = """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def step(x):
            return x, x * 2

        stepped = shard_map(step, mesh=mesh, in_specs=(P("dp"),),
                            out_specs=(P("dp"),))
    """
    findings = active(lint(src, only="SH03"))
    assert len(findings) == 1
    assert "out_specs has 1" in findings[0].message
    assert "2-tuple" in findings[0].message


def test_sh03_vararg_and_variable_specs_out_of_scope():
    src = """
        from jax import shard_map

        def step(*xs):
            return xs[0]

        stepped = shard_map(step, mesh=mesh, in_specs=specs,
                            out_specs=out)
    """
    assert active(lint(src, only="SH03")) == []


def test_sh03_same_named_nested_defs_disambiguate_by_lineno():
    # the sharded_embedding idiom: several builders each define `local`
    src = """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def build_a(mesh):
            def local(x):
                return x
            return shard_map(local, mesh=mesh, in_specs=(P("dp"),),
                             out_specs=P("dp"))

        def build_b(mesh):
            def local(x, y):
                return x + y
            return shard_map(local, mesh=mesh, in_specs=(P("dp"), P("dp")),
                             out_specs=P("dp"))
    """
    assert active(lint(src, only="SH03")) == []


# ------------------------------------------------------------------- SH04

SH04_BAD = """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    step = jax.jit(fn, donate_argnums=(0,),
                   in_shardings=(NamedSharding(mesh, P()),))

    def run(x):
        x = jax.device_put(x, NamedSharding(mesh, P("dp")))
        return step(x)
"""


def test_sh04_fires_on_placement_vs_declaration_mismatch():
    findings = active(lint(SH04_BAD, only="SH04"))
    assert len(findings) == 1
    assert "donated at position 0" in findings[0].message
    assert "use-after-free" in findings[0].message


def test_sh04_quiet_when_placement_matches():
    src = SH04_BAD.replace('jax.device_put(x, NamedSharding(mesh, P("dp")))',
                           'jax.device_put(x, NamedSharding(mesh, P()))')
    assert active(lint(src, only="SH04")) == []


def test_sh04_rebinding_clears_the_placed_signature():
    src = SH04_BAD.replace(
        "        return step(x)",
        "        x = transform(x)\n        return step(x)")
    assert active(lint(src, only="SH04")) == []


def test_sh04_variable_shardings_out_of_scope():
    src = """
        import jax

        step = jax.jit(fn, donate_argnums=(0,), in_shardings=shardings)

        def run(x):
            x = jax.device_put(x, sh)
            return step(x)
    """
    assert active(lint(src, only="SH04")) == []


# ------------------------------------------------------------------- NM01

NM01_LOGSUMEXP_BAD = """
    import jax.numpy as jnp

    def lse(x):
        return jnp.log(jnp.sum(jnp.exp(x)))
"""

NM01_SOFTMAX_BAD = """
    import jax.numpy as jnp

    def softmax(x):
        e = jnp.exp(x)
        return e / jnp.sum(e, axis=-1, keepdims=True)
"""


def test_nm01_fires_on_naive_logsumexp_in_ops():
    findings = active(lint(NM01_LOGSUMEXP_BAD, only="NM01",
                           path="ops/losses.py"))
    assert len(findings) == 1
    assert "logsumexp" in findings[0].message


def test_nm01_fires_on_named_exp_softmax_in_models():
    findings = active(lint(NM01_SOFTMAX_BAD, only="NM01",
                           path="models/transformer.py"))
    assert len(findings) == 1
    assert "softmax" in findings[0].message


def test_nm01_quiet_with_max_subtraction():
    src = """
        import jax.numpy as jnp

        def lse(x):
            m = jnp.max(x)
            return m + jnp.log(jnp.sum(jnp.exp(x - m)))
    """
    assert active(lint(src, only="NM01", path="ops/losses.py")) == []


def test_nm01_quiet_outside_ops_and_models():
    assert active(lint(NM01_LOGSUMEXP_BAD, only="NM01",
                       path="serving/engine.py")) == []


def test_nm01_clip_guard_quiets():
    src = """
        import jax.numpy as jnp

        def lse(x):
            x = jnp.clip(x, -30.0, 30.0)   # bounded by construction
            return jnp.log(jnp.sum(jnp.exp(x)))
    """
    assert active(lint(src, only="NM01", path="ops/losses.py")) == []


# ------------------------------------------- registry / pragmas / baseline

def test_registry_has_twenty_seven_rules_incl_disagg_tier():
    rules = all_rules()
    assert len(rules) == 27
    for rid in ("SH01", "SH02", "SH03", "SH04", "NM01", "CT01", "DG01"):
        assert rid in rules
        assert rules[rid].title


@pytest.mark.parametrize("rid,src", [
    ("SH01", SH01_BAD), ("SH02", SH02_BAD), ("SH03", SH03_IN_BAD),
    ("SH04", SH04_BAD), ("NM01", NM01_LOGSUMEXP_BAD),
])
def test_sharding_rules_pragma_roundtrip(rid, src):
    path = "ops/x.py" if rid == "NM01" else "snippet.py"
    findings = lint(src, only=rid, path=path)
    assert [f.status for f in findings] == [ACTIVE]
    line = textwrap.dedent(src).splitlines()[findings[0].line - 1]
    suppressed_src = textwrap.dedent(src).replace(
        line, line + f"  # graftlint: disable={rid}")
    findings = lint(suppressed_src, only=rid, path=path)
    assert [f.status for f in findings] == [SUPPRESSED]


@pytest.mark.parametrize("rid,src", [
    ("SH01", SH01_BAD), ("SH02", SH02_BAD), ("SH03", SH03_IN_BAD),
    ("SH04", SH04_BAD), ("NM01", NM01_SOFTMAX_BAD),
])
def test_sharding_rules_baseline_roundtrip(rid, src):
    path = "models/x.py" if rid == "NM01" else "snippet.py"
    findings = active(lint(src, only=rid, path=path))
    assert findings
    bl = Baseline.from_findings(findings, justification="pre-tier legacy")
    refound = lint(src, only=rid, baseline=bl, path=path)
    assert [f.status for f in refound] == [BASELINED]


def test_emit_metrics_publishes_sharding_gauges():
    from deeplearning4j_tpu import observability as obs

    obs.enable()
    obs.METRICS.reset()
    emit_metrics(lint(SH01_BAD, only="SH01"), registry=obs.METRICS,
                 skipped=3)
    gauges = obs.METRICS.snapshot()["gauges"]
    assert gauges["graftlint.violations.SH01"] == 1
    assert gauges["graftlint.violations.SH02"] == 0
    assert gauges["graftlint.skipped_files"] == 3


# --------------------------------------------------------- hostile inputs

def test_bad_syntax_fixture_skips_gracefully():
    fixture = os.path.join(FIXTURES, "graftlint_bad_syntax.py")
    analyzer = Analyzer()
    findings = analyzer.analyze_paths([fixture])
    assert findings == []
    assert analyzer.visited_files == 1
    assert analyzer.skipped_files == 1
    assert len(analyzer.errors) == 1 and "graftlint_bad_syntax" in \
        analyzer.errors[0]


def test_nul_byte_and_non_utf8_sources_skip_gracefully(tmp_path):
    nul = tmp_path / "nul.py"
    nul.write_text("x = 1\x00\n")
    binary = tmp_path / "bin.py"
    binary.write_bytes(b"\xff\xfe\x00\x00 not python")
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    analyzer = Analyzer()
    findings = analyzer.analyze_paths([str(tmp_path)])
    assert findings == []          # the good file still parsed clean
    assert analyzer.skipped_files == 2
    assert len(analyzer.errors) == 2
    # the skip is COUNTED, not silent: the gauge publishes it
    from deeplearning4j_tpu import observability as obs
    obs.enable()
    obs.METRICS.reset()
    emit_metrics(findings, registry=obs.METRICS,
                 skipped=analyzer.skipped_files)
    assert obs.METRICS.snapshot()["gauges"]["graftlint.skipped_files"] == 2


def test_crashing_rule_is_contained(monkeypatch):
    class Bomb:
        id = "XX99"
        title = "always crashes"

        def check(self, module):
            raise RuntimeError("boom")

    analyzer = Analyzer(rules=[Bomb(), all_rules()["SH02"]])
    findings = analyzer.analyze_source(
        textwrap.dedent(SH02_BAD), "snippet.py")
    assert rules_hit(findings) == {"SH02"}   # SH02 still ran
    assert any("XX99" in e for e in analyzer.errors)


# ------------------------------------------------------------- --diff mode

def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True, timeout=60)


@pytest.fixture
def diff_repo(tmp_path, monkeypatch):
    """A tiny two-file git repo with exactly one file changed since HEAD,
    with tools.graftlint retargeted at it."""
    repo = tmp_path / "repo"
    pkg = repo / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "clean.py").write_text("x = 1\n")
    (pkg / "dirty.py").write_text("y = 2\n")
    _git(repo, "init", "-q")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "seed")
    (pkg / "dirty.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "spec = P('dpx')\n")
    import tools.graftlint as gl

    monkeypatch.setattr(gl, "_REPO_ROOT", str(repo))
    return repo, gl


def test_diff_mode_visits_only_changed_files(diff_repo, capsys):
    repo, gl = diff_repo
    rc = gl.main(["--diff", "HEAD", "--json", "--no-metrics",
                  "--baseline", str(repo / "no-baseline.json"),
                  str(repo / "pkg")])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["visited_files"] == 1           # NOT a full-tree walk
    assert {f["rule"] for f in payload["findings"]} == {"SH02"}

    # and it agrees with the full run on the changed file's findings
    rc = gl.main(["--json", "--no-metrics",
                  "--baseline", str(repo / "no-baseline.json"),
                  str(repo / "pkg")])
    assert rc == 0
    full = json.loads(capsys.readouterr().out)
    assert full["visited_files"] == 2
    def key(f):
        return (f["rule"], os.path.basename(f["path"]), f["line"])
    assert ({key(f) for f in payload["findings"]}
            <= {key(f) for f in full["findings"]})
    assert ({key(f) for f in full["findings"] if "dirty" in f["path"]}
            == {key(f) for f in payload["findings"]})


def test_diff_mode_unknown_ref_falls_back_to_full_tree(diff_repo, capsys):
    repo, gl = diff_repo
    rc = gl.main(["--diff", "no-such-ref", "--json", "--no-metrics",
                  "--baseline", str(repo / "no-baseline.json"),
                  str(repo / "pkg")])
    assert rc == 0
    captured = capsys.readouterr()
    assert "falling back to full tree" in captured.err
    assert json.loads(captured.out)["visited_files"] == 2


def test_diff_mode_no_changes_short_circuits(diff_repo, capsys):
    repo, gl = diff_repo
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "absorb")
    rc = gl.main(["--diff", "HEAD", "--no-metrics",
                  "--baseline", str(repo / "no-baseline.json"),
                  str(repo / "pkg")])
    assert rc == 0
    assert "no .py files changed" in capsys.readouterr().out


# ------------------------------------------------------------- ShardGuard

@pytest.fixture
def mesh8():
    import jax
    from deeplearning4j_tpu.parallel.mesh import local_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    return local_mesh(8)


def _sharded(mesh, spec, n=8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    return jax.device_put(jnp.arange(float(n)), NamedSharding(mesh, spec))


def test_shardguard_explicit_mode_flags_drifted_input(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh8, P())
    g = ShardGuard().enable()
    f = g.wrap("t.step", jax.jit(lambda x: x + 1), in_shardings=(rep,))
    f(_sharded(mesh8, P()))
    assert not g.violations()
    f(_sharded(mesh8, P("dp")))
    assert g.counts()["resharded-input"] == 1
    [v] = [v for v in g.violations() if v.kind == "resharded-input"]
    assert v.site == "t.step"
    # the unchecked output side runs in baseline mode — the drifted
    # dispatch moved the result too, and the guard saw that as well
    assert g.counts()["resharded-output"] == 1


def test_shardguard_baseline_mode_flags_drift_not_first_placement(mesh8):
    import jax
    from jax.sharding import PartitionSpec as P

    g = ShardGuard().enable()
    f = g.wrap("t.step", jax.jit(lambda x: x * 2))
    f(_sharded(mesh8, P("dp")))          # first call SETS the baseline
    f(_sharded(mesh8, P("dp")))
    assert not g.violations()
    f(_sharded(mesh8, P()))              # later drift is the violation
    assert g.counts()["resharded-input"] == 1


def test_shardguard_output_expectations(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh8, P())
    dp = NamedSharding(mesh8, P("dp"))
    g = ShardGuard().enable()
    f = g.wrap("t.step", jax.jit(lambda x: x, out_shardings=dp),
               out_shardings=(rep,))
    f(_sharded(mesh8, P()))
    assert g.counts()["resharded-output"] == 1


def test_shardguard_disabled_costs_nothing_and_records_nothing(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    g = ShardGuard()          # never enabled
    f = g.wrap("t.step", jax.jit(lambda x: x + 1),
               in_shardings=(NamedSharding(mesh8, P()),))
    f(_sharded(mesh8, P("dp")))
    assert g.violations() == [] and g.counts()["resharded-input"] == 0


def test_shardguard_dedups_violations_but_counts_occurrences(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    g = ShardGuard().enable()
    f = g.wrap("t.step", jax.jit(lambda x: x + 1),
               in_shardings=(NamedSharding(mesh8, P()),))
    for _ in range(3):
        f(_sharded(mesh8, P("dp")))
    assert len(g.violations()) == 1          # one finding per leaf/site
    assert g.counts()["resharded-input"] == 3  # every dispatch counted


def test_shardguard_reset_and_report(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    g = ShardGuard().enable()
    assert g.report() == "shardguard: clean (0 violations)"
    f = g.wrap("t.step", jax.jit(lambda x: x + 1),
               in_shardings=(NamedSharding(mesh8, P()),))
    f(_sharded(mesh8, P("dp")))
    assert "t.step" in g.report()
    g.reset()
    assert g.violations() == [] and g.counts()["resharded-input"] == 0


def test_shardguard_emit_metrics_gauges(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu import observability as obs

    g = ShardGuard().enable()
    f = g.wrap("t.step", jax.jit(lambda x: x + 1),
               in_shardings=(NamedSharding(mesh8, P()),))
    f(_sharded(mesh8, P("dp")))
    f(_sharded(mesh8, P("dp")))
    obs.enable()
    obs.METRICS.reset()
    g.emit_metrics()
    gauges = obs.METRICS.snapshot()["gauges"]
    assert gauges["shardguard.violations.resharded_input"] == 2
    assert gauges["shardguard.violations.resharded_output"] == 0


def test_shardguard_wrapper_forwards_lower(mesh8):
    import jax

    g = ShardGuard()
    f = g.wrap("t.step", jax.jit(lambda x: x + 1))
    lowered = f.lower(_sharded(mesh8, jax.sharding.PartitionSpec()))
    assert lowered.compile() is not None


@pytest.mark.shardguard
def test_shardguard_marker_enables_the_singleton(mesh8):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert SHARDGUARD.enabled
    f = SHARDGUARD.wrap("t.clean", jax.jit(lambda x: x + 1),
                        in_shardings=(NamedSharding(mesh8, P()),))
    f(_sharded(mesh8, P()))     # clean dispatch: the fixture's teardown
    # assertion (zero violations) is the actual test


def test_shardguard_trainer_sync_step_clean_under_guard():
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.optimize import transforms as T
    from deeplearning4j_tpu.parallel import DataParallelTrainer

    def loss_fn(p, x, y, key=None):
        return ((x @ p["w"] - y) ** 2).mean()

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(4, 1))
    x = rng.normal(size=(16, 4)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)

    with shardguard_active() as g:
        tr = DataParallelTrainer(loss_fn, T.sgd_lr(0.05))
        state = tr.init_state({"w": np.zeros((4, 1), np.float32)})
        for _ in range(3):
            state, _ = tr.step(state, x, y)
        assert not g.violations(), g.report()


# --------------------------------------------------- seeded regression

PLANTED = """
    import jax
    import numpy as np
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def step(x):
        return lax.psum(x, "tp")          # planted: wrong-axis collective

    stepped = shard_map(step, mesh=mesh, in_specs=(P("dpz"),),
                        out_specs=P("dp"))  # planted: axis nobody creates
"""


def test_seeded_regression_static_and_runtime(mesh8):
    """Acceptance seed: the planted wrong-axis collective and mismatched
    axis name are caught statically (SH01 + SH02), and the same class of
    mistake made at runtime — dispatching against a drifted placement —
    is counted live by ShardGuard."""
    findings = active(lint(PLANTED))
    assert {"SH01", "SH02"} <= rules_hit(findings)

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu import observability as obs

    g = ShardGuard().enable()
    f = g.wrap("regress.step", jax.jit(lambda x: x + 1),
               in_shardings=(NamedSharding(mesh8, P()),))
    f(_sharded(mesh8, P()))
    assert not g.violations()
    f(_sharded(mesh8, P("dp")))          # the runtime mismatch, counted
    assert g.counts()["resharded-input"] == 1
    obs.enable()
    obs.METRICS.reset()
    g.emit_metrics()
    assert obs.METRICS.snapshot()["gauges"][
        "shardguard.violations.resharded_input"] == 1
