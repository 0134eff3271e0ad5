"""The benchmark's harness off the chip (CPU, tiny configurations).

What can be checked without a TPU: that the runners' window functions run
end to end and count what they should, that the arithmetic behind the
end-to-end metrics is right, that every data file is well formed and agrees
with ``BENCHMARK.json``, that a cell added as files is found by name with no
code edited, and that ``run.py`` refuses to run.  Nothing here is a device
number.  ``conftest.py`` has the compile cache off and eight virtual devices.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import flops, harness  # noqa: E402
from benchmark.runners import serve, train  # noqa: E402
from deeplearning4j_tpu.observability import METRICS  # noqa: E402

TINY = Path(__file__).resolve().parent / "tiny"
BENCH = REPO / "benchmark"
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
DATA_FILES = sorted(p.relative_to(BENCH).as_posix()
                    for kind in ("workloads", "configs", "layer_metrics")
                    for p in (BENCH / kind).glob("*.json"))


def tiny_cell(workload: str, seconds: float, **changes) -> harness.Cell:
    w = dict(harness.load("workloads", workload, TINY), **changes)
    return harness.Cell(
        workload=w, config=harness.load("configs", w["config"], TINY),
        seed=2**31 + 11, seconds=seconds, devices=jax.devices()[:w["chips"]],
        process_t0=time.perf_counter())


# ---------------------------------------------------------------- the runners

@pytest.mark.parametrize("n_dp,zero_stage", [(1, 0), (4, 1)])
def test_train_window_counts_steps_and_compiles_once(n_dp, zero_stage):
    if len(jax.devices()) < n_dp:
        pytest.skip(f"needs {n_dp} virtual devices")
    cell = tiny_cell("tiny_bert.train", 1.5, n_dp=n_dp, zero_stage=zero_stage)
    w, cfg = cell.workload, harness.transformer_config(cell.config)
    METRICS.reset()
    trainer, state = train.build(cfg, w, cell.seed)
    pool = train.host_batches(cfg.vocab_size, w["global_batch"], w["seq_len"],
                              w["pool_batches"], cell.seed)
    state, warm = trainer.fit(state, pool[:2], resolve_every=w["resolve_every"])
    opened = []
    state, losses, wall = train.window(trainer, state, pool, cell.seconds,
                                       w["resolve_every"], opened.append)
    assert len(opened) == 1 and len(losses) >= 8 and wall >= cell.seconds
    assert METRICS.snapshot()["counters"]["train_step.recompile"] == 1
    checks = train.judge(warm[0], w["first_loss_band"], losses, 0)
    assert all(ok for ok, _ in checks), checks


def test_train_run_reports_its_metric_and_facts():
    out = train.run(tiny_cell("tiny_bert.train", 1.0))
    assert out.correct and out.attempted > 0 and out.failed == 0
    assert out.end_to_end["train_tokens_per_s"] > 0 and out.end_to_end["setup_s"] > 0
    f = out.facts
    assert f["tokens_per_step"] == 8 * 64 and f["chips"] == 1
    assert f["flops_per_token"] == 6.0 * (2 * (4 * 64 * 64 + 2 * 64 * 128)
                                          + 512 * 64 + 2 * 2 * 64 * 64)


@pytest.mark.parametrize("losses,first,moved,ok", [
    ([5.0] * 8 + [4.0] * 8, 6.2, 0, True),
    ([5.0] * 8 + [5.5] * 8, 6.2, 0, False),            # rising
    ([5.0] * 8 + [float("nan")] * 8, 6.2, 0, False),   # not finite
    ([5.0] * 8 + [4.0] * 8, 9.9, 0, False),            # first loss off the band
    ([5.0] * 8 + [4.0] * 8, 6.2, 1, False),            # compiled in the window
    ([], 6.2, 0, False),
])
def test_train_judge(losses, first, moved, ok):
    assert all(c for c, _ in train.judge(first, (6.0, 6.7), losses, moved)) is ok


def test_serve_run_completes_requests_and_judges_them():
    out = serve.run(tiny_cell("tiny_gpt.textgen", 2.0))
    assert out.correct and out.attempted > 8 and out.failed == 0
    for name in ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"):
        assert out.end_to_end[name] > 0
    assert out.facts["timers"]["serving.decode_step"]["count"] > 0
    assert not out.facts["counters"].get("serving.prefill.recompile")


def test_serve_requests_same_sizes_for_every_seed_and_distinct_prompts():
    w = harness.load("workloads", "tiny_gpt.textgen", TINY)
    a, b = (serve.make_requests(dict(w, n_requests=64), 50257, s) for s in (1, 2**31 + 5))
    sizes = [sorted((len(p), n) for p, n in r[:16]) for r in (a, b)]
    assert sizes[0] == sizes[1] and a[:16] != b[:16]
    assert sorted((len(p), n) for p, n in a[16:32]) == sizes[0]
    assert len({tuple(p[:4]) for p, _ in a if len(p) >= 4}) > 55
    assert a == serve.make_requests(dict(w, n_requests=64), 50257, 1)


# ------------------------------------------------------------- the arithmetic

@pytest.mark.parametrize("values,q,expected", [
    ([1.0], 0.95, 1.0),
    ([1.0, 2.0], 0.5, 1.5),
    (list(range(1, 101)), 0.95, 95.05),
    (list(range(1, 101)), 0.50, 50.5),
    ([5.0, 1.0, 3.0], 1.0, 5.0),
    ([5.0, 1.0, 3.0], 0.0, 1.0),
])
def test_percentile_interpolates_like_numpy(values, q, expected):
    assert harness.percentile(values, q) == pytest.approx(expected)


def test_serving_metrics_from_hand_made_completions():
    def done(i, t_sub, t_done, n, ttft):
        return (i, t_sub, t_done, SimpleNamespace(
            tokens=list(range(n)), finish_reason="length", ttft_s=ttft), None)

    requests = [([1, 2], 11), ([1], 21), ([3], 5), ([4], 11), ([5], 7)]
    records = [
        done(0, 9.0, 11.0, 11, 0.5),     # tpot (2.0 - 0.5) / 10 = 150 ms
        done(1, 10.0, 14.0, 21, 1.0),    # tpot (4.0 - 1.0) / 20 = 150 ms
        done(3, 12.0, 19.0, 11, 2.0),    # tpot (7.0 - 2.0) / 10 = 500 ms
        done(2, 5.0, 9.5, 5, 0.1),       # came back before the window
        done(4, 15.0, 20.0, 7, 0.1),     # came back at the deadline: outside
        (4, 13.0, 13.5, None, RuntimeError("refused")),
    ]
    m = serve.serving_metrics(records, requests, 10.0, 20.0)
    assert (m["attempted"], m["failed"], len(m["completed"])) == (4, 1, 3)
    assert m["output_tokens"] == 43 and m["as_asked"]
    assert m["serve_tokens_per_s"] == pytest.approx(4.3)
    assert m["ttft_p50_ms"] == pytest.approx(1000.0)
    assert m["ttft_p95_ms"] == pytest.approx(1900.0)     # 1000 + 0.9 * 1000
    assert m["tpot_p50_ms"] == pytest.approx(150.0)
    assert m["tpot_p95_ms"] == pytest.approx(465.0)      # 150 + 0.9 * 350
    short = serve.serving_metrics([done(0, 9.0, 11.0, 9, 0.5)], requests, 10.0, 20.0)
    assert not short["as_asked"]


def test_analytic_flops_of_bert_base():
    model = harness.load("configs", "bert_base")["transformer_config"]
    assert flops.matmul_params(model) == 110_100_480
    assert flops.train_flops_per_token(model, 512) == 6.0 * (110_100_480 + 9_437_184)


# ------------------------------------------------------------- the data files

@pytest.mark.parametrize("rel", DATA_FILES)
def test_data_file_is_well_formed(rel):
    kind, name = rel.split("/")[0], Path(rel).stem
    spec = harness.load(kind, name)
    assert spec["name"] == name and harness.NAME.fullmatch(name)
    if kind == "workloads":
        assert harness.load("configs", spec["config"])["name"] == spec["config"]
        assert hasattr(harness.load_module("runners", spec["runner"]), "run")
        assert spec["chips"] in (1, 4) and 1 <= len(spec["why"]) and spec["who"]
        a, b = spec["trace_slice_s"]
        assert 0 <= a < b <= MANIFEST["run_seconds"]
    elif kind == "configs":
        harness.transformer_config(spec)
        assert spec["source"] and isinstance(spec["reduced"], list)
        assert all(harness.NAME.fullmatch(k) for k in spec["reduced"])
    else:
        assert hasattr(harness.load_module("readers", spec["reader"]), "read")
        assert harness.UNIT.fullmatch(spec["unit"]) and spec["layer"] and spec["moves"]


def test_manifest_agrees_with_the_data_files():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for name, w in cells.items():
        spec = harness.load("workloads", name)
        assert (spec["config"], spec["chips"]) == (w["config"], w["chips"])
        assert name == f"{w['config']}.{w['traffic']}" and len(w["why"]) <= 200
        assert len(harness.cell_metrics(MANIFEST, "end_to_end", name)) >= 2
        assert harness.cell_metrics(MANIFEST, "per_layer", name)
    for c in MANIFEST["configs"]:
        spec = json.loads((REPO / c["file"]).read_text())
        assert (spec["name"], spec["source"], spec["reduced"]) == (
            c["name"], c["source"], c["reduced"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert harness.NAME.fullmatch(m["name"]) and harness.UNIT.fullmatch(m["unit"])
        assert set(m.get("workloads", [])) <= set(cells)
    for m in MANIFEST["per_layer"]:
        spec = harness.load("layer_metrics", m["name"])
        assert m["moves"] in e2e
        for key in ("unit", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        moved = next(x for x in MANIFEST["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


# ------------------------------------------------- found by name, refuses CPU

def run_py(repo: Path, *args: str):
    return subprocess.run(
        [sys.executable, str(repo / "benchmark" / "run.py"), *args, "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
        timeout=300, cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def no_result_line(proc) -> bool:
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_run_py_refuses_to_run_without_a_tpu(cell):
    proc = run_py(REPO, "--workload", cell)
    assert proc.returncode != 0 and no_result_line(proc)
    assert "'cpu'" in proc.stderr and "Nothing was run" in proc.stderr


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copy(TINY / "configs" / "tiny_gpt.json", tmp_path / "benchmark" / "configs")
    shutil.copy(TINY / "workloads" / "tiny_gpt.textgen.json",
                tmp_path / "benchmark" / "workloads")
    found = run_py(tmp_path, "--workload", "tiny_gpt.textgen")
    # every file of the new cell was found; only the missing chip stops it
    assert found.returncode != 0 and "needs a TPU" in found.stderr, found.stderr
    missing = run_py(tmp_path, "--workload", "tiny_gpt.nope")
    assert missing.returncode != 0 and "no benchmark/workloads/tiny_gpt.nope.json" \
        in missing.stderr
    assert no_result_line(found) and no_result_line(missing)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchmarkError, match="not in benchmark/peaks.json"):
        harness.load_peak("cpu")
    assert harness.load_peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
