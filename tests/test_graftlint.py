"""graftlint unit tests.

Every rule is demonstrated on a known-bad fixture snippet AND shown quiet
on the corresponding known-good rewrite — the shipped tree only exercises
a subset of the rules, so this file is where each rule's trigger contract
actually lives.  Also covers the suppression pragmas, the baseline
ledger, the metrics gauges, and the ``tools.graftlint`` CLI.
"""

import json
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


def lint(source, only=None, baseline=None, path="snippet.py"):
    """Analyze one dedented snippet; ``only`` restricts to a single rule
    so known-good assertions aren't polluted by a *different* rule firing
    on the same fixture."""
    rules = [all_rules()[only]] if only else None
    analyzer = Analyzer(rules=rules, baseline=baseline)
    findings = analyzer.analyze_source(textwrap.dedent(source), path)
    assert not analyzer.errors
    return findings


def rules_hit(findings):
    return {f.rule for f in findings if f.status == ACTIVE}


# --------------------------------------------------------------------------- HS01

HS01_BAD = """
    import jax

    step = jax.jit(lambda p, x: p * x)

    def fit(p, xs):
        total = 0.0
        for x in xs:
            loss = step(p, x)
            total += float(loss)
        return total
"""


def test_hs01_fires_on_float_in_loop():
    findings = [f for f in lint(HS01_BAD) if f.rule == "HS01"]
    assert len(findings) == 1
    assert "float(loss)" in findings[0].code
    assert "drain" in findings[0].message


def test_hs01_fires_in_loop_free_per_call_function():
    src = """
        import jax

        step = jax.jit(lambda p, x: p * x)

        def apply_step(p, x):
            loss = step(p, x)
            return float(loss)
    """
    findings = [f for f in lint(src) if f.rule == "HS01"]
    assert len(findings) == 1
    assert "loop-free" in findings[0].message


def test_hs01_quiet_on_post_loop_fence():
    src = """
        import jax

        step = jax.jit(lambda p, x: p * x)

        def fit(p, xs):
            loss = None
            for x in xs:
                loss = step(p, x)
            return float(loss)
    """
    assert lint(src, only="HS01") == []


def test_hs01_quiet_on_untainted_values():
    src = """
        def fit(xs):
            total = 0.0
            for x in xs:
                total += float(x)
            return total
    """
    assert lint(src, only="HS01") == []


# --------------------------------------------------------------------------- RC01

def test_rc01_fires_on_param_dependent_shape():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def embed(n, x):
            return jnp.arange(n) + x
    """
    findings = [f for f in lint(src) if f.rule == "RC01"]
    assert len(findings) == 1
    assert "'n'" in findings[0].message


def test_rc01_quiet_on_shape_derived_sizes():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def embed(x):
            return jnp.arange(x.shape[0]) + x
    """
    assert lint(src, only="RC01") == []


def test_rc01_fires_on_list_literal_at_static_position():
    src = """
        import jax

        agg = jax.jit(lambda x, dims: x, static_argnums=(1,))

        def call(x):
            return agg(x, [1, 2])
    """
    findings = [f for f in lint(src) if f.rule == "RC01"]
    assert len(findings) == 1
    assert "hashable" in findings[0].message


def test_rc01_quiet_on_tuple_at_static_position():
    src = """
        import jax

        agg = jax.jit(lambda x, dims: x, static_argnums=(1,))

        def call(x):
            return agg(x, (1, 2))
    """
    assert lint(src, only="RC01") == []


# --------------------------------------------------------------------------- RNG01

def test_rng01_fires_on_sequential_reuse():
    src = """
        import jax

        def sample(key):
            a = jax.random.normal(key)
            b = jax.random.uniform(key)
            return a + b
    """
    findings = [f for f in lint(src) if f.rule == "RNG01"]
    assert len(findings) == 1
    assert "correlated" in findings[0].message


def test_rng01_fires_on_cross_iteration_reuse():
    src = """
        import jax

        def roll(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(key))
            return out
    """
    findings = [f for f in lint(src) if f.rule == "RNG01"]
    assert len(findings) == 1
    assert "every" in findings[0].message


def test_rng01_quiet_on_split_keys():
    src = """
        import jax

        def sample(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1)
            b = jax.random.uniform(k2)
            return a + b
    """
    assert lint(src, only="RNG01") == []


def test_rng01_quiet_on_per_iteration_fold_in():
    src = """
        import jax

        def roll(key, n):
            out = []
            for i in range(n):
                key = jax.random.fold_in(key, i)
                out.append(jax.random.normal(key))
            return out
    """
    # key is rebound in the loop body — and the fold_in/normal pair within
    # one iteration draws from DIFFERENT values of the rebound name
    assert lint(src, only="RNG01") == []


def test_rng01_quiet_across_exclusive_branches():
    src = """
        import jax

        def pick(key, flag):
            if flag:
                return jax.random.normal(key)
            return jax.random.uniform(key)
    """
    assert lint(src, only="RNG01") == []


# --------------------------------------------------------------------------- DON01

DON01_PRELUDE = """
    import jax

    step = jax.jit(lambda p, x: p + x, donate_argnums=(0,))
"""


def test_don01_fires_on_read_after_donation():
    src = DON01_PRELUDE + """
    def train(p, x):
        q = step(p, x)
        y = p + 1
        return q, y
    """
    findings = [f for f in lint(src) if f.rule == "DON01"]
    assert len(findings) == 1
    assert "donated" in findings[0].message


def test_don01_fires_on_unrebound_donation_in_loop():
    src = DON01_PRELUDE + """
    def train(p, xs):
        q = None
        for x in xs:
            q = step(p, x)
        return q
    """
    findings = [f for f in lint(src) if f.rule == "DON01"]
    assert len(findings) == 1
    assert "next iteration" in findings[0].message


def test_don01_quiet_when_rebound_from_result():
    src = DON01_PRELUDE + """
    def train(p, xs):
        for x in xs:
            p = step(p, x)
        return p
    """
    assert lint(src, only="DON01") == []


# --------------------------------------------------------------------------- TB01

def test_tb01_fires_on_python_if_over_traced_param():
    src = """
        import jax

        @jax.jit
        def relu(x):
            if x > 0:
                return x
            return 0.0
    """
    findings = [f for f in lint(src) if f.rule == "TB01"]
    assert len(findings) == 1
    assert "lax.cond" in findings[0].message


def test_tb01_quiet_on_static_attribute_tests():
    src = """
        import jax

        @jax.jit
        def maybe_pad(x):
            if x.shape[0] > 2:
                return x
            return x * 2.0
    """
    assert lint(src, only="TB01") == []


def test_tb01_quiet_on_is_none_tests():
    src = """
        import jax

        @jax.jit
        def f(x, key):
            if key is None:
                return x
            return x + 1
    """
    assert lint(src, only="TB01") == []


def test_tb01_quiet_outside_traced_functions():
    src = """
        def plain(x):
            if x > 0:
                return x
            return 0.0
    """
    assert lint(src, only="TB01") == []


# --------------------------------------------------------------------------- HOT02

HOT02_BAD = """
    import jax

    step = jax.jit(lambda p: p * 2)

    def run(p, n):
        for _ in range(n):
            p = step(p)
        return p
"""


def test_hot02_fires_on_uninstrumented_dispatch_loop():
    findings = [f for f in lint(HOT02_BAD) if f.rule == "HOT02"]
    assert len(findings) == 1
    assert "instrumentation" in findings[0].message


def test_hot02_quiet_with_metrics_counter_in_loop():
    src = """
        import jax

        step = jax.jit(lambda p: p * 2)

        def run(p, n):
            for _ in range(n):
                p = step(p)
                METRICS.increment("run.steps")
            return p
    """
    assert lint(src, only="HOT02") == []


def test_hot02_quiet_with_span_around_loop():
    src = """
        import jax

        step = jax.jit(lambda p: p * 2)

        def run(p, n):
            with trace.span("run", steps=n):
                for _ in range(n):
                    p = step(p)
            return p
    """
    assert lint(src, only="HOT02") == []


def test_hot02_quiet_on_host_only_loops():
    src = """
        def run(xs):
            out = []
            for x in xs:
                out.append(x * 2)
            return out
    """
    assert lint(src, only="HOT02") == []


# --------------------------------------------------------------------------- EXC01

EXC01_BAD = """
    def retry(fn, attempts=3):
        for _ in range(attempts):
            try:
                return fn()
            except:
                continue
"""


def test_exc01_fires_on_bare_except():
    findings = [f for f in lint(EXC01_BAD) if f.rule == "EXC01"]
    assert len(findings) == 1
    assert "SystemExit" in findings[0].message


def test_exc01_quiet_on_typed_handlers():
    src = """
        def retry(fn, attempts=3, retry_on=(Exception,)):
            for _ in range(attempts):
                try:
                    return fn()
                except retry_on:
                    continue
                except Exception:
                    raise
    """
    assert lint(src, only="EXC01") == []


# --------------------------------------------------------------------------- PL01

PL01_BAD = """
    from jax.experimental import pallas as pl

    def call(kernel, x, spec):
        return pl.pallas_call(kernel, out_shape=x, in_specs=[spec])(x)
"""


def test_pl01_fires_on_pallas_call_without_interpret():
    findings = [f for f in lint(PL01_BAD) if f.rule == "PL01"]
    assert len(findings) == 1
    assert "interpret" in findings[0].message


def test_pl01_quiet_when_interpret_is_threaded():
    src = """
        from jax.experimental import pallas as pl

        def call(kernel, x, spec, interpret):
            return pl.pallas_call(kernel, out_shape=x, in_specs=[spec],
                                  interpret=interpret)(x)
    """
    assert lint(src, only="PL01") == []


# --------------------------------------------------------------------------- ZR01

ZR01_BAD = """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def init_state(self, params):
        stage = self.zero_stage
        tstate = self.transform.init(params)
        tstate = jax.device_put(tstate, NamedSharding(self.mesh, P()))
        return tstate
"""

ZR01_BAD_TREE_MAP = """
    import jax

    def restore(self, template):
        stage = self.zero_stage
        tstate = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._rep_sh), template.tstate)
        return tstate
"""

ZR01_GOOD_GATED = """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def init_state(self, params):
        if self.zero_stage >= 1:
            tstate = self.init_sharded(params)
        else:
            tstate = jax.device_put(self.transform.init(params),
                                    NamedSharding(self.mesh, P()))
        return tstate
"""

ZR01_GOOD_EARLY_RETURN = """
    import jax

    def restore(self, template):
        if self.zero_stage >= 1:
            return self._restore_zero(template)
        return jax.device_put(template.tstate, self._rep_sh)
"""

ZR01_GOOD_NOT_ZERO_AWARE = """
    import jax

    def init_state(self, params):
        # stage-0-only trainer: replicating state is the correct layout
        tstate = self.transform.init(params)
        return jax.device_put(tstate, self._rep_sh)
"""


def test_zr01_fires_on_ungated_replicated_tstate_put():
    findings = [f for f in lint(ZR01_BAD) if f.rule == "ZR01"]
    assert len(findings) == 1
    assert "zero_stage" in findings[0].message
    assert "1/ndp" in findings[0].message


def test_zr01_fires_on_tree_map_device_put_form():
    findings = [f for f in lint(ZR01_BAD_TREE_MAP) if f.rule == "ZR01"]
    assert len(findings) == 1


def test_zr01_quiet_when_gated_by_zero_stage_branch():
    assert lint(ZR01_GOOD_GATED, only="ZR01") == []


def test_zr01_quiet_after_early_returning_zero_stage_guard():
    assert lint(ZR01_GOOD_EARLY_RETURN, only="ZR01") == []


def test_zr01_quiet_in_functions_that_never_read_zero_stage():
    assert lint(ZR01_GOOD_NOT_ZERO_AWARE, only="ZR01") == []


def test_zr01_quiet_on_dp_sharded_placement():
    src = """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def init_state(self, params):
            stage = self.zero_stage
            tstate = self.transform.init(params)
            return jax.device_put(tstate, NamedSharding(self.mesh, P("dp")))
    """
    assert lint(src, only="ZR01") == []


# --------------------------------------------------------------------------- suppressions

def test_same_line_pragma_suppresses_one_rule():
    src = HS01_BAD.replace(
        "total += float(loss)",
        "total += float(loss)  # graftlint: disable=HS01")
    findings = [f for f in lint(src) if f.rule == "HS01"]
    assert len(findings) == 1
    assert findings[0].status == SUPPRESSED
    assert active(findings) == []


def test_comment_line_pragma_applies_to_next_statement():
    src = HS01_BAD.replace(
        "total += float(loss)",
        "# deliberate per-step read  # graftlint: disable=HS01\n"
        "            total += float(loss)")
    findings = [f for f in lint(src) if f.rule == "HS01"]
    assert [f.status for f in findings] == [SUPPRESSED]


def test_file_wide_pragma():
    src = "# graftlint: disable-file=HS01\n" + textwrap.dedent(HS01_BAD)
    findings = [f for f in lint(src) if f.rule == "HS01"]
    assert [f.status for f in findings] == [SUPPRESSED]


def test_bare_disable_silences_every_rule_on_the_line():
    src = HS01_BAD.replace(
        "total += float(loss)",
        "total += float(loss)  # graftlint: disable")
    findings = [f for f in lint(src) if f.rule == "HS01"]
    assert [f.status for f in findings] == [SUPPRESSED]


def test_pragma_for_other_rule_does_not_suppress():
    src = HS01_BAD.replace(
        "total += float(loss)",
        "total += float(loss)  # graftlint: disable=RC01")
    assert "HS01" in rules_hit(lint(src))


# --------------------------------------------------------------------------- baseline

def test_baseline_roundtrip_and_matching(tmp_path):
    findings = active(lint(HS01_BAD))
    assert findings
    bl = Baseline.from_findings(findings, justification="legacy hot path")
    path = tmp_path / "baseline.json"
    bl.save(str(path))

    loaded = Baseline.load(str(path))
    assert loaded.entries == bl.entries
    assert all(loaded.contains(f) for f in findings)

    # with the baseline applied the same findings classify as baselined
    refound = lint(HS01_BAD, baseline=loaded)
    assert [f.status for f in refound if f.rule == "HS01"] == [BASELINED]
    assert active([f for f in refound if f.rule == "HS01"]) == []


def test_baseline_is_line_number_free(tmp_path):
    bl = Baseline.from_findings(active(lint(HS01_BAD)))
    # shift every line down: the (rule, path, code) key still matches
    shifted = "\n# padding\n# padding\n" + textwrap.dedent(HS01_BAD)
    findings = Analyzer(baseline=bl).analyze_source(shifted, "snippet.py")
    assert [f.status for f in findings if f.rule == "HS01"] == [BASELINED]


def test_baseline_invalidated_by_editing_the_flagged_line():
    bl = Baseline.from_findings(active(lint(HS01_BAD)))
    edited = HS01_BAD.replace("total += float(loss)",
                              "total += 2.0 * float(loss)")
    findings = lint(edited, baseline=bl)
    assert "HS01" in rules_hit(findings)  # forced a fresh look


def test_baseline_dedupes_identical_code_lines():
    src = """
        import jax

        step = jax.jit(lambda p, x: p * x)

        def fit_a(p, xs):
            for x in xs:
                loss = step(p, x)
                print(float(loss))

        def fit_b(p, xs):
            for x in xs:
                loss = step(p, x)
                print(float(loss))
    """
    findings = [f for f in active(lint(src)) if f.rule == "HS01"]
    assert len(findings) == 2
    bl = Baseline.from_findings(findings)
    assert len(bl.entries) == 1  # same (rule, path, code) key


def test_stale_entries_reported_after_fix():
    bl = Baseline.from_findings(
        [f for f in active(lint(HS01_BAD)) if f.rule == "HS01"])
    fixed = HS01_BAD.replace("total += float(loss)", "total = loss")
    findings = lint(fixed, baseline=bl)
    stale = bl.stale_entries(findings)
    assert len(stale) == len(bl.entries) == 1


def test_baseline_load_missing_file_is_empty(tmp_path):
    bl = Baseline.load(str(tmp_path / "nope.json"))
    assert bl.entries == []


def test_baseline_load_rejects_foreign_json(tmp_path):
    p = tmp_path / "other.json"
    p.write_text(json.dumps([1, 2, 3]))
    with pytest.raises(ValueError):
        Baseline.load(str(p))


# --------------------------------------------------------------------------- metrics

def test_emit_metrics_publishes_per_rule_gauges():
    from deeplearning4j_tpu import observability as obs

    obs.enable()
    obs.METRICS.reset()
    findings = lint(HS01_BAD)
    emit_metrics(findings, registry=obs.METRICS)

    snap = obs.METRICS.snapshot()
    assert snap["counters"]["graftlint.runs"] == 1
    assert snap["gauges"]["graftlint.violations.HS01"] == 1
    # rules with no hits still publish an explicit zero (scrapable absence)
    assert snap["gauges"]["graftlint.violations.DON01"] == 0
    assert snap["gauges"]["graftlint.violations.total"] == len(
        active(findings))


def test_emit_metrics_counts_only_active_findings():
    from deeplearning4j_tpu import observability as obs

    obs.enable()
    obs.METRICS.reset()
    suppressed = HS01_BAD.replace(
        "total += float(loss)",
        "total += float(loss)  # graftlint: disable=HS01")
    emit_metrics(lint(suppressed), registry=obs.METRICS)
    assert obs.METRICS.snapshot()["gauges"]["graftlint.violations.HS01"] == 0


# --------------------------------------------------------------------------- CLI

def _write(tmp_path, name, source):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return str(p)


def test_cli_check_succeeds_on_clean_file(tmp_path):
    from tools.graftlint import main

    path = _write(tmp_path, "ok.py", "x = 1\n")
    assert main([path, "--check", "--no-metrics",
                 "--baseline", str(tmp_path / "b.json")]) == 0


def test_cli_check_fails_on_new_violation(tmp_path, capsys):
    from tools.graftlint import main

    path = _write(tmp_path, "bad.py", HS01_BAD)
    assert main([path, "--check", "--no-metrics",
                 "--baseline", str(tmp_path / "b.json")]) == 1
    out = capsys.readouterr().out
    assert "HS01" in out and "bad.py" in out


def test_cli_check_fails_on_parse_error(tmp_path, capsys):
    from tools.graftlint import main

    path = _write(tmp_path, "broken.py", "def f(:\n")
    assert main([path, "--check", "--no-metrics",
                 "--baseline", str(tmp_path / "b.json")]) == 1
    assert "parse error" in capsys.readouterr().err


def test_cli_write_baseline_then_check_is_clean(tmp_path, capsys):
    from tools.graftlint import main

    path = _write(tmp_path, "bad.py", HS01_BAD)
    bfile = str(tmp_path / "b.json")
    assert main([path, "--write-baseline", "--no-metrics",
                 "--baseline", bfile]) == 0
    assert main([path, "--check", "--no-metrics", "--baseline", bfile]) == 0
    capsys.readouterr()
    # the accepted finding shows up as baselined in the JSON report
    assert main([path, "--json", "--no-metrics", "--baseline", bfile]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["tool"] == "graftlint"
    assert payload["summary"]["baselined"] >= 1
    assert payload["summary"]["active"] == 0


def test_cli_rules_filter_and_unknown_rule(tmp_path, capsys):
    from tools.graftlint import main

    path = _write(tmp_path, "bad.py", HS01_BAD)
    bfile = str(tmp_path / "b.json")
    # HS01 filtered out: only HOT02 can fire on this fixture
    assert main([path, "--check", "--no-metrics", "--baseline", bfile,
                 "--rules", "RC01,TB01"]) == 0
    capsys.readouterr()
    assert main([path, "--no-metrics", "--rules", "NOPE"]) == 2
    assert "unknown rule" in capsys.readouterr().err


# --------------------------------------------------------------------------- OB01

OB01_BAD = """
    import time
    import jax

    step = jax.jit(lambda p, x: p * x)

    def decode(p, x):
        t0 = time.perf_counter()
        out = step(p, x)
        return out, time.perf_counter() - t0
"""

OB01_GOOD = """
    import time
    import jax
    from deeplearning4j_tpu.observability import METRICS

    step = jax.jit(lambda p, x: p * x)

    def decode(p, x):
        t0 = time.perf_counter()
        out = step(p, x)
        METRICS.observe_time("serving.decode_step", time.perf_counter() - t0)
        return out
"""

OB01_GOOD_RECORD_SPAN = """
    import time
    import jax
    from deeplearning4j_tpu.observability import trace

    step = jax.jit(lambda p, x: p * x)

    def decode(p, x):
        t0 = time.perf_counter()
        out = step(p, x)
        trace.record_span("serving.decode", t0, time.perf_counter() - t0)
        return out
"""


def test_ob01_fires_on_raw_timing_of_dispatch_in_serving():
    findings = lint(OB01_BAD, only="OB01",
                    path="deeplearning4j_tpu/serving/snippet.py")
    assert rules_hit(findings) == {"OB01"}


def test_ob01_fires_in_parallel_tree_too():
    findings = lint(OB01_BAD, only="OB01",
                    path="deeplearning4j_tpu/parallel/snippet.py")
    assert rules_hit(findings) == {"OB01"}


def test_ob01_quiet_outside_serving_and_parallel():
    assert not lint(OB01_BAD, only="OB01",
                    path="deeplearning4j_tpu/models/snippet.py")


def test_ob01_quiet_when_measurement_reaches_registry():
    assert not lint(OB01_GOOD, only="OB01",
                    path="deeplearning4j_tpu/serving/snippet.py")


def test_ob01_quiet_when_measurement_reaches_tracer():
    assert not lint(OB01_GOOD_RECORD_SPAN, only="OB01",
                    path="deeplearning4j_tpu/serving/snippet.py")


def test_ob01_quiet_on_clock_without_dispatch():
    src = """
        import time

        def backoff(attempt):
            t0 = time.monotonic()
            return t0 + 2.0 ** attempt
    """
    assert not lint(src, only="OB01",
                    path="deeplearning4j_tpu/serving/snippet.py")


# --------------------------------------------------------------------------- QT01

QT01_BAD = """
    import jax.numpy as jnp

    def pack(kv):
        return kv.astype(jnp.int8)
"""

QT01_BAD_FP8 = """
    import jax.numpy as jnp

    def pack(kv):
        return kv.astype(jnp.float8_e4m3fn)
"""

QT01_BAD_KWARG = """
    import jax.numpy as jnp

    def pack(kv):
        return kv.astype(dtype=jnp.int8)
"""

QT01_GOOD = """
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.kv_quant import cast_to

    def pack(kv, scale):
        return cast_to(kv / scale, jnp.int8)
"""


def test_qt01_fires_on_raw_int8_cast_in_serving():
    findings = lint(QT01_BAD, only="QT01",
                    path="deeplearning4j_tpu/serving/snippet.py")
    assert rules_hit(findings) == {"QT01"}


def test_qt01_fires_on_fp8_and_dtype_kwarg_in_models():
    for src in (QT01_BAD_FP8, QT01_BAD_KWARG):
        findings = lint(src, only="QT01",
                        path="deeplearning4j_tpu/models/snippet.py")
        assert rules_hit(findings) == {"QT01"}


def test_qt01_quiet_outside_serving_and_models():
    """The quant helpers themselves (ops/pallas) hold the one allowed
    raw cast — the rule scopes to the consumer trees."""
    assert not lint(QT01_BAD, only="QT01",
                    path="deeplearning4j_tpu/ops/pallas/kv_quant.py")


def test_qt01_quiet_on_helper_and_float_casts():
    assert not lint(QT01_GOOD, only="QT01",
                    path="deeplearning4j_tpu/serving/snippet.py")
    src = """
        import jax.numpy as jnp

        def widen(x):
            return x.astype(jnp.float32)
    """
    assert not lint(src, only="QT01",
                    path="deeplearning4j_tpu/serving/snippet.py")


# --------------------------------------------------------------------------- EL01

EL01_BAD = """
    import jax
    from jax.sharding import Mesh

    def build():
        m = Mesh(jax.devices(), ("dp",))
        first_eight = jax.devices()[:8]
        chip = jax.local_devices()[0]
        return m, first_eight, chip
"""

EL01_GOOD = """
    import jax
    from deeplearning4j_tpu.parallel.mesh import elastic_mesh

    def build(n):
        return elastic_mesh(jax.devices()[:n])
"""


def test_el01_fires_on_raw_mesh_and_literal_device_slice():
    findings = lint(EL01_BAD, only="EL01",
                    path="deeplearning4j_tpu/parallel/snippet.py")
    assert rules_hit(findings) == {"EL01"}
    assert len(findings) == 3           # Mesh(...) + [:8] + [0]
    findings = lint(EL01_BAD, only="EL01",
                    path="deeplearning4j_tpu/resilience/snippet.py")
    assert len(findings) == 3           # resilience/ is in scope too


def test_el01_quiet_on_helpers_and_variable_slices():
    """Variable-bounded slices are the sanctioned idiom: the width is a
    parameter the caller re-derives after a resize (driver.py/dryrun.py)."""
    assert not lint(EL01_GOOD, only="EL01",
                    path="deeplearning4j_tpu/parallel/snippet.py")


def test_el01_scoped_to_parallel_and_resilience():
    """mesh.py is the one sanctioned construction site; trees outside
    parallel/+resilience/ (tools, tests, serving) are out of scope."""
    assert not lint(EL01_BAD, only="EL01",
                    path="deeplearning4j_tpu/parallel/mesh.py")
    assert not lint(EL01_BAD, only="EL01",
                    path="deeplearning4j_tpu/serving/snippet.py")
    assert not lint(EL01_BAD, only="EL01", path="tools/snippet.py")


# --------------------------------------------------------------------------- OB02

OB02_BAD = """
    from deeplearning4j_tpu.observability import METRICS
    def work(registry):
        METRICS.increment("serving.bogus_counter")
        registry.gauge("made.up.gauge", 1.0)
        with METRICS.time("undocumented.timer"):
            pass
"""

OB02_GOOD = """
    from deeplearning4j_tpu.observability import METRICS
    def work(site, registry):
        METRICS.increment("serving.requests")
        METRICS.increment(f"faults.injected.{site}")
        METRICS.gauge("goodput.seconds." + "stall", 1.0)
        registry.gauge("goodput.fraction", 0.5)
        name = compute_name()
        METRICS.increment(name)          # runtime-composed: out of scope
        other.increment("not.a.registry.recv")
"""


def _ob02(source, documented):
    from deeplearning4j_tpu.analysis.rules import UndocumentedMetricNameRule
    UndocumentedMetricNameRule.set_documented(documented)
    try:
        return lint(source, only="OB02",
                    path="deeplearning4j_tpu/serving/snippet.py")
    finally:
        UndocumentedMetricNameRule.set_documented(None)


def test_ob02_fires_on_undocumented_names():
    findings = _ob02(OB02_BAD, ["serving.requests"])
    assert rules_hit(findings) == {"OB02"}
    assert len(findings) == 3            # increment + gauge + time
    assert any("serving.bogus_counter" in f.message for f in findings)


def test_ob02_quiet_on_documented_and_wildcard_names():
    documented = ["serving.requests", "faults.injected.<site>",
                  "goodput.seconds.<state>", "goodput.fraction"]
    assert not _ob02(OB02_GOOD, documented)


def test_ob02_fstring_prefix_checked_against_wildcards():
    """An f-string's leading literal must overlap a wildcard row; a
    fully documented exact row also covers names built under it."""
    src = """
        from deeplearning4j_tpu.observability import METRICS
        def work(rule):
            METRICS.gauge(f"graftlint.violations.{rule}", 1.0)
    """
    assert not _ob02(src, ["graftlint.violations.<rule>"])
    findings = _ob02(src, ["serving.requests"])
    assert len(findings) == 1
    assert "graftlint.violations." in findings[0].message


def test_ob02_package_tables_cover_the_tree():
    """The committed README/DESIGN tables must cover every name the
    package emits — the zero-baseline contract for this rule."""
    from deeplearning4j_tpu.analysis import Analyzer, active
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    analyzer = Analyzer(rules=[all_rules()["OB02"]], root=repo)
    findings = analyzer.analyze_paths(
        [os.path.join(repo, "deeplearning4j_tpu")])
    assert [f for f in active(findings)] == []
