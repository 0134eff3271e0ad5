"""``benchmark/trace_spans.py`` and the three readers on it (``module_time``,
``scope_share``, ``idle_cause``): the wire-format parser on a hand-written
``XSpace``, the arithmetic on hand-written rows (nested scopes, a backward
wrapper, a gap under two nested spans, a gap under none, a clipped execution
left out, the host plane's clock moved onto the device's), and the identities
the metrics promise on a cut-down sample of a real trace of
``bert_base.train_b64``.  No chip, and no device number."""

import json
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness, trace_reduce as tr, trace_spans as ts  # noqa: E402
from benchmark.readers import idle_cause, module_time, scope_share  # noqa: E402

TPU0, OPS, MODS = "/device:TPU:0", tr.OP_LINE, tr.MODULE_LINE
SCOPES = scope_share.SUBLAYERS


def op(name, start, dur, path="", plane=TPU0):
    """An operation row in microseconds."""
    return (plane, name, start * 1e3, dur * 1e3, path)


def trace_rows(ops, modules):
    """``run["trace_rows"]`` for hand-written operation rows and
    ``(name, start_us, dur_us)`` module executions."""
    return ([(r[0], OPS, r[1], r[2], r[3]) for r in ops]
            + [(TPU0, MODS, n, s * 1e3, d * 1e3) for n, s, d in modules])


# ------------------------------------------------------------ the scope path

@pytest.mark.parametrize("path,want", [
    ("jit(step)/jvp(vmap(attention))/bqhd,bkhd->bqhk/dot_general:",
     ("attention", None, False)),
    ("jit(step)/transpose(jvp(vmap(attention)))/bqhk,bkhd->bqhd/dot_general:",
     ("attention", None, True)),
    # a kernel's registered name inside a sublayer stays with the sublayer
    ("jit(step)/jvp(attention)/attention.flash/pallas_call:",
     ("attention", "attention.flash", False)),
    # nested sublayers: the outermost wins, the next is shown
    ("jit(decode_step)/ffn/layernorm/mul:", ("ffn", "layernorm", False)),
    ("jit(step)/shard_map/checkpoint(rematted_computation(ffn))/dot_general:",
     ("ffn", None, False)),
    ("jit(step)/optimizer/sub:", ("optimizer", None, False)),
    ("jit(step)/transpose(jvp(vmap(while)))/body/closed_call/mul:",
     (None, None, True)),
    ("jit(step)/xent.blocked/reduce_sum:", (None, "xent.blocked", False)),
    ("", (None, None, False)),
])
def test_scope_of_takes_the_outermost_sublayer_through_wrappers(path, want):
    assert ts.scope_of(path, SCOPES) == want


# whole step 100..400 us: attention forward 100, its backward 50, ffn 100 with a
# nested operation of another scope, 50 us unnamed; a clipped step before it
STEP_OPS = [
    op("clipped", 0, 90, "jit(step)/jvp(attention)/exp:"),
    op("a", 100, 100, "jit(step)/jvp(vmap(attention))/dot_general:"),
    op("b", 200, 50, "jit(step)/transpose(jvp(vmap(attention)))/dot_general:"),
    op("while", 250, 100, "jit(step)/jvp(ffn)/while:"),
    op("body", 260, 40, "jit(step)/jvp(ffn)/while/body/attention.flash/x:"),
    op("c", 350, 50, ""),
    op("tail", 405, 50, "jit(step)/jvp(ffn)/x:"),
]
STEP_MODULES = [("jit_step(1)", 0, 95), ("jit_step(1)", 100, 300),
                ("jit_other(2)", 402, 2), ("jit_step(1)", 404, 60)]


def test_scope_table_counts_self_time_inside_whole_executions_only():
    rows = trace_rows(STEP_OPS, STEP_MODULES)
    runs = tr.whole_runs(rows, "jit_step")
    assert runs == {TPU0: [(100e3, 400e3)]}       # the two at the ends are clipped
    inside = ts.inside(STEP_OPS, runs)
    assert [r[1] for r in inside] == ["a", "b", "while", "body", "c"]
    tab = scope_share.table(STEP_OPS, runs)
    assert tab["attention"]["fwd"] == 100e3 and tab["attention"]["bwd"] == 50e3
    assert tab["ffn"]["fwd"] == 100e3             # the while AND its body
    assert dict(tab["ffn"]["inner"]) == {"attention.flash": 40e3}
    assert tab[None]["fwd"] == 50e3
    total = sum(t["fwd"] + t["bwd"] for t in tab.values())
    busy = tr.busy_idle([r for r in rows if r[1] == OPS], (100e3, 400e3))["busy_ns"]
    assert total == busy == 300e3


def test_scope_table_of_a_program_without_named_scopes_is_nothing():
    bare = [op("a", 100, 100, "jit(step)/jvp(vmap(while))/dot_general:"),
            op("b", 200, 100, "")]
    assert scope_share.table(bare, {TPU0: [(100e3, 400e3)]}) is None


# ----------------------------------------------------------- idle, by a span

def test_idle_under_nested_spans_goes_to_the_innermost():
    gaps = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0), (60.0, 70.0)]
    host = [("trainer.fence", 0.0, 25.0, "main"),
            ("trainer.fence.read", 5.0, 8.0, "main"),
            ("train_step", 22.0, 45.0, "main"),
            ("trainer.fit", -100.0, 100.0, "main")]
    by = ts.idle_by_span(gaps, host)
    # gap 1: fence 0-5 and 8-10, read 5-8; gap 2: fence 20-22, then train_step
    # (started later) 22-30; gap 3: train_step 40-45, fit 45-50; gap 4: fit
    assert by == {"trainer.fence": 9.0, "trainer.fence.read": 3.0,
                  "train_step": 13.0, "trainer.fit": 15.0}
    assert sum(by.values()) == sum(b - a for a, b in gaps)
    assert ts.idle_by_span(gaps, []) == {None: 40.0}
    assert idle_cause.under(by, ["trainer.fence"]) == 12.0     # and its children
    assert idle_cause.under(by, ["train_step"]) == 13.0
    assert idle_cause.under(by, ["train"]) == 0.0              # not a prefix of names
    assert idle_cause.under({None: 4.0, "x.y": 1.0}, []) == 4.0


def test_idle_intervals_are_the_complement_of_the_union():
    busy = [(0.0, 40.0), (30.0, 50.0), (60.0, 80.0), (100.0, 200.0), (120.0, 140.0)]
    gaps, window = ts.idle_intervals(busy)
    assert gaps == [(50.0, 60.0), (80.0, 100.0)] and window == (0.0, 200.0)
    rows = [(TPU0, OPS, "x", s, e - s) for s, e in busy]
    assert sum(b - a for a, b in gaps) == pytest.approx(
        tr.busy_idle(rows)["idle_share"] * 200.0)


def test_clock_offsets_are_bounded_by_enqueue_and_completion():
    modules = [(TPU0, 7, 1000.0, 2000.0), (TPU0, 8, 2100.0, 2200.0),
               (TPU0, 9, 5000.0, 5100.0), ("/device:TPU:1", 7, 0.0, 10.0)]
    launches = [(ts.ENQUEUED, 0, 7, 1300.0),       # host ahead by >= 300
                (ts.ENQUEUED, 0, 8, 1500.0),       # queued early: a weak bound
                (ts.COMPLETED, 0, 7, 2500.0),      # ahead by <= 500
                (ts.COMPLETED, 0, 8, 2900.0),
                (ts.ENQUEUED, 0, 99, 0.0),         # no such execution traced
                (ts.ENQUEUED, 1, 7, 50.0)]         # chip 1: no upper bound
    assert ts.clock_offsets(modules, launches) == {TPU0: (400.0, 300.0, 500.0)}
    assert ts.clock_offsets(modules, []) == {}


def idle_run(shift_us=0.0):
    """Two steps with a 100 us gap between them: 30 us of it under
    ``trainer.fence.read``, 50 us under ``train_step``, 20 us under nothing
    but ``trainer.fit``; the host plane ``shift_us`` ahead of the device."""
    ops = [op("a", 0, 1000), op("b", 1100, 1000)]
    rows = trace_rows(ops, [("jit_step(1)", 0, 1000), ("jit_step(1)", 1100, 1000)])
    k = 1e3
    host = [(n, (s + shift_us) * k, (e + shift_us) * k, "python3") for n, s, e in [
        ("trainer.fit", -50, 2000), ("trainer.fence.wait", 10, 1005),
        ("trainer.fence.read", 1005, 1030), ("train_step", 1050, 1400)]]
    parsed = {"ops": ops, "host": host, "launches": [], "modules": []}
    if shift_us:
        parsed["modules"] = [(TPU0, 1, 0.0, 1000 * k), (TPU0, 2, 1100 * k, 2100 * k)]
        parsed["launches"] = [(ts.ENQUEUED, 0, 2, (1100 + shift_us - 10) * k),
                              (ts.COMPLETED, 0, 1, (1000 + shift_us + 10) * k)]
    return rows, parsed


@pytest.mark.parametrize("shift_us", [0.0, 1500.0])
def test_idle_table_adds_up_to_the_idle_share(shift_us):
    rows, parsed = idle_run(shift_us)
    by, window = idle_cause.table(rows, parsed)
    assert window == 2100e3
    assert by == pytest.approx({"trainer.fence.wait": 5e3, "trainer.fence.read": 25e3,
                                "train_step": 50e3, None: 20e3})
    assert sum(by.values()) / window == pytest.approx(
        tr.reduce(rows)["idle_share_worst"])
    assert idle_cause.under(by, ["trainer.fence"]) == pytest.approx(30e3)


def test_idle_table_of_a_program_without_annotations_is_nothing():
    rows, parsed = idle_run()
    assert idle_cause.table(rows, dict(parsed, host=[])) is None


# ------------------------------------------------ the file, and read() itself

XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 14000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000
             stats { metadata_id: 5 uint64_value: 1 } }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 3000000
             stats { metadata_id: 5 uint64_value: 2 } }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 6000000
             stats { metadata_id: 5 uint64_value: 3 } } }
  lines { id: 3 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8,128]{1,0} fusion()"
      stats { metadata_id: 1 str_value: "jit(step)/jvp(vmap(attention))/dot_general:" }
      stats { metadata_id: 3 ref_value: 4 } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8]{0} copy()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(42)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.9 = f32[8]{0} fusion()"
      stats { metadata_id: 1 str_value: "jit(step)/transpose(jvp(ffn))/mul:" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "hlo_category" } }
  stat_metadata { key: 4 value { id: 4 name: "convolution fusion" } }
  stat_metadata { key: 5 value { id: 5 name: "run_id" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000000 }
    events { metadata_id: 2 offset_ps: 5600000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 } }
  lines { id: 8 name: "main/280" timestamp_ns: 500
    events { metadata_id: 4 offset_ps: 6400000 duration_ps: 10000
             stats { metadata_id: 1 str_value: "2" } stats { metadata_id: 2 str_value: "0" } }
    events { metadata_id: 5 offset_ps: 9700000 duration_ps: 10000
             stats { metadata_id: 1 str_value: "2" } } }
  event_metadata { key: 1 value { id: 1 name: "PjitFunction(step)" } }
  event_metadata { key: 2 value { id: 2 name: "trainer.fence.read" } }
  event_metadata { key: 3 value { id: 3 name: "train_step" } }
  event_metadata { key: 4 value { id: 4 name: "DoEnqueueProgram" } }
  event_metadata { key: 5 value { id: 5 name: "CompleteCallbacks" } }
  stat_metadata { key: 1 value { id: 1 name: "run_id" } }
  stat_metadata { key: 2 value { id: 2 name: "device_ordinal" } } }
"""


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """The hand-written ``XSpace`` as the file ``run.py`` would have left, and
    the ``run`` the readers are given for it."""
    path = tmp_path / "cell" / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    monkeypatch.setattr(ts, "TRACE_ROOT", tmp_path)
    rows = tr.read_xspace(path)
    return path, {"trace_rows": rows, "trace": tr.reduce(rows), "facts": {}}


def test_parse_reads_what_profile_data_reads_and_the_metadata_stats(traced):
    path, run = traced
    parsed = ts.parse(path)
    assert ts.parse(path) is parsed                     # parsed once
    assert [(r[0], r[1], r[2], r[3]) for r in parsed["ops"]] == [
        (p, n, s, d) for p, line, n, s, d in run["trace_rows"] if line == OPS]
    assert [r[4] for r in parsed["ops"]] == [
        "jit(step)/jvp(vmap(attention))/dot_general:", "",
        "jit(step)/transpose(jvp(ffn))/mul:",
        "jit(step)/jvp(vmap(attention))/dot_general:", ""]
    # the runtime's own TraceMes are not the program's spans
    assert parsed["host"] == [("trainer.fence.read", 6100.0, 6400.0, "python3"),
                              ("train_step", 6500.0, 8500.0, "python3")]
    assert parsed["modules"] == [(TPU0, 1, 1000.0, 6000.0), (TPU0, 2, 7000.0, 10000.0),
                                 (TPU0, 3, 11000.0, 17000.0)]
    assert parsed["launches"] == [(ts.ENQUEUED, 0, 2, 6900.0),
                                  (ts.COMPLETED, 0, 2, 10200.0)]
    assert ts.of_run(run) is parsed
    assert ts.of_run({"trace_rows": None}) is None
    assert ts.of_run(dict(run, trace_rows=run["trace_rows"][1:])) is None   # another trace


def test_the_three_readers_read_the_file(traced, capsys):
    _, run = traced
    # one whole execution (7-10 us): the ffn fusion alone
    assert module_time.read({"module_prefix": "jit_step"}, run) == pytest.approx(0.003)
    assert module_time.read({"module_prefix": "jit_none"}, run) is None
    share = lambda scopes: scope_share.read(  # noqa: E731
        {"module_prefix": "jit_step", "scopes": scopes}, run)
    assert share(["ffn"]) == pytest.approx(100.0)
    assert share(["attention"]) == 0.0 and share([]) == 0.0
    assert scope_share.read({"module_prefix": "jit_none", "scopes": []}, run) is None
    # device idle 6-7 us and 10-11 us of 1-17 us; the host plane is ahead by
    # -100..200 ns, so taken as 50: fence.read 6050-6350, train_step 6450-8450
    idle = lambda spans: idle_cause.read({"spans": spans}, run)  # noqa: E731
    assert idle(["trainer.fence"]) == pytest.approx(100 * 300 / 16000)
    assert idle(["train_step"]) == pytest.approx(100 * 550 / 16000)
    assert idle(["trainer.data_wait"]) == 0.0
    assert idle([]) == pytest.approx(100 * 1150 / 16000)
    total = sum(idle(s) for s in (["trainer.fence"], ["train_step"], []))
    assert total == pytest.approx(100 * run["trace"]["idle_share_worst"])
    out = capsys.readouterr().out
    assert out.count("by sublayer") == 1 and out.count("by the host's phase") == 1
    assert "host plane ahead of /device:TPU:0 by 0 us" in out


def test_a_file_that_cannot_be_read_is_nothing_not_an_error(traced, capsys):
    path, run = traced
    path.write_bytes(b"\x0b\x0b\x0b")            # a wire type xplane.proto lacks
    assert ts.of_run(run) is None
    assert scope_share.read({"module_prefix": "jit_step", "scopes": []}, run) is None
    assert idle_cause.read({"spans": []}, run) is None
    assert "could not read" in capsys.readouterr().out


def test_readers_find_nothing_without_a_trace_file(tmp_path, monkeypatch):
    monkeypatch.setattr(ts, "TRACE_ROOT", tmp_path)
    run = {"trace_rows": trace_rows(STEP_OPS, STEP_MODULES), "facts": {}}
    assert scope_share.read({"module_prefix": "jit_step", "scopes": []}, run) is None
    assert idle_cause.read({"spans": []}, run) is None
    assert module_time.read({"module_prefix": "jit_step"}, run) == pytest.approx(0.3)
    assert module_time.read({"module_prefix": "jit_step"}, {"trace_rows": None}) is None


# ------------------------------------------------------ the recorded sample

@pytest.fixture(scope="module")
def sample():
    """A cut-down real trace of ``bert_base.train_b64`` (the note in the file
    says how it was cut): operation rows with their scope paths, the
    program's spans, and the runtime's launch events."""
    raw = json.loads((Path(__file__).parent / "trace_spans_train.json").read_text())
    ops = [(TPU0, name, start, dur, raw["paths"][i]) for name, start, dur, i in raw["ops"]]
    modules = [tuple(m) for m in raw["modules"]]
    rows = ([(TPU0, OPS, n, s, d) for _, n, s, d, _ in ops]
            + [(TPU0, MODS, name, s, e - s) for name, _, s, e in modules])
    parsed = {"ops": ops, "host": [tuple(h) for h in raw["host"]],
              "modules": [(TPU0, rid, s, e) for _, rid, s, e in modules],
              "launches": [tuple(x) for x in raw["launches"]]}
    return raw, rows, parsed


def test_sample_scope_table_adds_up_and_names_the_costliest_sublayer(sample):
    raw, rows, parsed = sample
    runs = tr.whole_runs(rows, "jit_step")
    assert len(runs[TPU0]) == raw["whole_steps"]
    tab = scope_share.table(parsed["ops"], runs)
    total = sum(t["fwd"] + t["bwd"] for t in tab.values())
    share = {k: 100 * (t["fwd"] + t["bwd"]) / total for k, t in tab.items()}
    assert sum(share.values()) == pytest.approx(100.0, abs=0.1)
    busy = sum(tr.busy_idle([r for r in rows if r[1] == OPS], span)["busy_ns"]
               for span in runs[TPU0])
    assert total == pytest.approx(busy, rel=1e-9)
    assert share[None] < 10.0
    assert max(share, key=share.get) == "attention" and share["attention"] > 35.0
    assert set(share) >= {"attention", "ffn", "qkv_proj", "attn_out", "lm_head_loss",
                          "layernorm", "embed", "optimizer"}
    assert tab["attention"]["bwd"] > 0 and tab["attention"]["fwd"] > 0


def test_sample_idle_parts_add_up_to_the_idle_share(sample):
    _, rows, parsed = sample
    by, window = idle_cause.table(rows, parsed)
    parts = [100 * idle_cause.under(by, spans) / window for spans in (
        ["trainer.fence"], ["train_step"], ["trainer.data_wait"], [])]
    whole = 100 * tr.reduce(rows)["idle_share_worst"]
    assert sum(parts) == pytest.approx(whole, abs=0.01)
    assert parts[3] < whole / 4                        # unattributed
    assert parts[0] > parts[1] > parts[2] >= 0         # fence, dispatch, data_wait
    ahead, lo, hi = ts.clock_offsets(parsed["modules"], parsed["launches"])[TPU0]
    assert 0 < lo < ahead < hi < 5e6                   # a millisecond or two


def test_sample_step_time_agrees_with_the_rate_of_the_run(sample):
    raw, rows, _ = sample
    step_ms = module_time.read({"module_prefix": "jit_step"}, {"trace_rows": rows})
    assert 300 < step_ms < 310
    idle = tr.reduce(rows)["idle_share_worst"]
    # the slice's own steps per second: whole steps over the time they span
    runs = tr.whole_runs(rows, "jit_step")[TPU0]
    per_s = (len(runs) - 1) / ((runs[-1][0] - runs[0][0]) / 1e9)
    assert step_ms * per_s == pytest.approx(1000 * (1 - idle), rel=0.01)


def test_new_metric_files_name_their_readers_arguments():
    for path in sorted((REPO / "benchmark" / "layer_metrics").glob("*.json")):
        spec = json.loads(path.read_text())
        if spec["reader"] == "scope_share":
            assert set(spec["args"]["scopes"]) <= set(SCOPES)
            assert spec["args"]["module_prefix"].startswith("jit_")
        elif spec["reader"] == "idle_cause":
            assert all(ts.PROGRAM_SPAN.match(s) for s in spec["args"]["spans"])
        elif spec["reader"] == "module_time":
            assert spec["args"]["module_prefix"].startswith("jit_")
    assert harness.load("layer_metrics", "step_device_ms.train")["reader"] == "module_time"
