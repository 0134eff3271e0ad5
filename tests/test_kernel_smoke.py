"""Tier-1 coverage of tools/kernel_smoke.py and the kernel tier's lint
hygiene: the microbench must run every registered candidate and publish
per-kernel timing through the observability layer, and ops/pallas must be
graftlint-clean with ZERO baseline entries (the kernel tier is new code —
it gets no legacy-debt ledger)."""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from deeplearning4j_tpu.analysis import Analyzer, Baseline, active  # noqa: E402
from deeplearning4j_tpu.observability import METRICS  # noqa: E402
from tools import kernel_smoke  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "graftlint.baseline.json")
PALLAS = os.path.join(REPO, "deeplearning4j_tpu", "ops", "pallas")


def test_kernel_smoke_runs_every_candidate_and_records_metrics():
    from deeplearning4j_tpu.ops.pallas import registry
    out = kernel_smoke.run()
    assert out["perf_claim"] is False
    expected = {f"{kind}.{c.name}" for kind in registry.kinds()
                for c in registry.candidates(kind)}
    assert set(out["kernels"]) == expected
    for rec in out["kernels"].values():
        assert rec["us_per_call"] > 0
        assert rec["bytes_moved_est"] > 0
    snap = METRICS.snapshot()
    for key in expected:
        assert f"kernel.{key}" in snap["timers"], key
        assert f"kernel.{key}.bytes_per_call" in snap["gauges"], key


def test_pallas_tier_is_lint_clean_with_zero_baseline_entries():
    analyzer = Analyzer(baseline=Baseline.load(BASELINE), root=REPO)
    findings = analyzer.analyze_paths([PALLAS])
    assert analyzer.errors == []
    fresh = active(findings)
    listing = "\n".join(
        f"  {f.path}:{f.line}: {f.rule} {f.message}" for f in fresh)
    assert not fresh, f"ops/pallas must stay lint-clean:\n{listing}"
    # no legacy-debt ledger for new code: the baseline must not mention
    # the kernel tier at all
    pallas_entries = [e for e in Baseline.load(BASELINE).entries
                     if "ops/pallas" in e.get("path", "")]
    assert pallas_entries == []
