"""``benchmark/trace_reduce.py`` on hand-written traces and on a cut-down
real one: busy time is a union, idle share of a known layout, the ranking of
operations by self time, gaps named by their neighbours, whole executions of
a program, and a host-only trace as an error."""

import json
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import busy_roofline, trace_idle  # noqa: E402

TPU0, TPU1, OPS, MODS = "/device:TPU:0", "/device:TPU:1", tr.OP_LINE, tr.MODULE_LINE


def op(name, start, dur, plane=TPU0, line=OPS, unit=1.0):
    return (plane, line, name, float(start) * unit, float(dur) * unit)


def us(name, start, dur, line=OPS):
    return op(name, start, dur, line=line, unit=1e3)


#  a: 0-----40        c: 60--80       d: 100-----------------200
#       b: 30---50                       e:  120--140 (inside d)
LAYOUT = [op("a", 0, 40), op("b", 30, 20), op("c", 60, 20), op("d", 100, 100),
          op("e", 120, 20)]


def test_overlapping_operations_are_a_union_not_a_sum():
    b = tr.busy_idle(LAYOUT)
    assert b["busy_ns"] == 50 + 20 + 100            # the durations sum to 200
    assert b["window_ns"] == 200
    assert b["idle_share"] == pytest.approx(30 / 200)


def test_gaps_are_named_by_their_neighbours():
    gaps = sorted(tr.busy_idle(LAYOUT)["gaps"], reverse=True)
    assert gaps == [(20.0, "c -> d"), (10.0, "b -> c")]
    inside = tr.busy_idle(LAYOUT, span=(-10.0, 230.0))
    assert (10.0, "(start) -> a") in inside["gaps"]
    assert (30.0, "d -> (end)") in inside["gaps"]
    assert inside["busy_ns"] == 170 and inside["window_ns"] == 240
    clipped = tr.busy_idle(LAYOUT, span=(35.0, 110.0))
    assert clipped["busy_ns"] == 15 + 20 + 10 and clipped["window_ns"] == 75


def test_operations_are_ranked_by_self_time():
    s = tr.self_times(LAYOUT)
    # an overlap goes to the operation that started later, so that self
    # times add up to the busy time
    assert s == {"a": 30, "b": 20, "c": 20, "d": 80, "e": 20}
    assert sum(s.values()) == tr.busy_idle(LAYOUT)["busy_ns"]
    nested = [op("while", 0, 100), op("body", 10, 80), op("leaf", 20, 30)]
    assert tr.self_times(nested) == {"while": 20, "body": 50, "leaf": 30}
    assert tr.top([("x", 1e9), ("y", 3e9), ("x", 1.5e9), ("z", 1e8)], n=2) == [
        ["y", 3.0], ["x", 2.5]]


@pytest.mark.parametrize("text,want", [
    ("%fusion.3029 = (f32[64,512,32768]{2,1,0:T(8,128)}, bf16[64,512]{1,0}) "
     "fusion(f32[32768,768]{1,0} %custom-call.8), kind=kOutput", "fusion f32[64,512,32768]"),
    ("%bitcast_reduce_fusion.10.remat = f32[64,512,12]{1,2,0} fusion(...)",
     "bitcast_reduce_fusion.remat f32[64,512,12]"),
    ("%copy-done.1 = f32[32768,768]{1,0:T(8,128)} copy-done(...)", "copy-done f32[32768,768]"),
    ("%slice-start.374 = ((f32[64,512,12]{1,2,0}), f32[16,512,12]) async-start(...)",
     "slice-start"),
    ("jit_step(123)", "jit_step(123)"),
])
def test_short_names_add_up_the_copies_of_an_operation(text, want):
    assert tr.short(text) == want


def test_reduce_over_two_chips_reports_the_worst_idle_share():
    rows = LAYOUT + [op("a", 0, 100, TPU1), op("b", 150, 50, TPU1),
                     op("host", 0, 1000, "/host:CPU", "python3")]
    r = tr.reduce(rows)
    assert set(r["planes"]) == {TPU0, TPU1}
    assert r["idle_share_worst"] == pytest.approx(50 / 200)
    assert r["busy_s"] == pytest.approx((170 + 150) / 2 / 1e9)
    assert r["window_s"] == pytest.approx(200 / 1e9)
    assert r["device_ops"][0] == ["a", pytest.approx(130 / 1e9)]    # 30 + 100
    assert r["idle_gaps"][0] == ["a -> b", pytest.approx(50 / 1e9)]
    assert trace_idle.read({}, {"trace": r}) == pytest.approx(25.0)
    assert trace_idle.read({}, {"trace": None}) is None


def test_a_host_only_trace_is_an_error_not_an_idle_device():
    with pytest.raises(tr.TraceError, match="no /device:TPU"):
        tr.reduce([op("x", 0, 10, "/host:CPU", "python3")])
    with pytest.raises(tr.TraceError, match="no 'XLA Ops' events"):
        tr.reduce([op("jit_step(1)", 0, 10, line=MODS)])


def test_whole_runs_leave_out_what_the_trace_clipped():
    rows = [us("jit_step(7)", 0, 50, line=MODS),        # clipped at the start
            us("jit_step(7)", 60, 100, line=MODS),
            us("jit_other(9)", 165, 5, line=MODS),
            us("jit_step(7)", 180, 100, line=MODS),
            us("jit_step(7)", 290, 10, line=MODS),      # clipped at the end
            us("x", 0, 300)]
    assert tr.whole_runs(rows, "jit_step") == {TPU0: [(60e3, 160e3), (180e3, 280e3)]}
    assert tr.whole_runs(rows, "jit_none") == {}


def test_busy_roofline_counts_whole_steps_over_busy_time():
    # two whole steps of 100 us, the device busy for 160 of the 220 us they span
    rows = [us("jit_step(7)", 0, 20, line=MODS), us("jit_step(7)", 30, 100, line=MODS),
            us("jit_step(7)", 150, 100, line=MODS), us("jit_step(7)", 260, 40, line=MODS),
            us("x", 0, 20), us("m", 30, 80), us("m", 150, 80), us("y", 260, 40)]
    run = {"facts": {"flops_per_token": 1e3, "tokens_per_step": 8, "chips": 1},
           "peak": {"bf16_flops_per_s": 1e9}, "trace_rows": rows}
    # 2 steps x 8000 FLOPs / 1e9 FLOP/s = 16 us at the roofline, busy 160 us
    assert busy_roofline.read({"module_prefix": "jit_step"}, run) == pytest.approx(10.0)
    assert busy_roofline.read({"module_prefix": "jit_none"}, run) is None
    assert busy_roofline.read({"module_prefix": "jit_step"}, dict(run, trace_rows=None)) is None


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8,128]{1,0} fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8]{0} copy()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(42)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000000 } }
  event_metadata { key: 1 value { id: 1 name: "host work" } } }
"""


def test_rows_of_a_hand_written_xspace():
    rows = tr.rows_of(ProfileData.from_text_proto(XSPACE))
    assert len(rows) == 4 and {r[0] for r in rows} == {TPU0}     # host plane left out
    r = tr.reduce(rows)
    # ops at 1000-6000, 4000-8000, 10000-11000 ns: busy 8000 of 10000
    assert r["planes"][TPU0] == {"window_ns": 10000.0, "busy_ns": 8000.0,
                                 "idle_share": pytest.approx(0.2)}
    assert r["device_ops"] == [["fusion f32[8,128]", pytest.approx(4e-6)],
                               ["copy f32[8]", pytest.approx(4e-6)]] or \
        r["device_ops"] == [["copy f32[8]", pytest.approx(4e-6)],
                            ["fusion f32[8,128]", pytest.approx(4e-6)]]
    assert r["idle_gaps"] == [["copy f32[8] -> fusion f32[8,128]", pytest.approx(2e-6)]]


def test_cut_down_real_trace_of_the_train_cell():
    """Every ``XLA Modules`` event and the long operations of a real 3 s slice
    of ``bert_base.train_b64`` (the note in the file says how it was cut)."""
    rows = [tuple(r) for r in json.loads(
        (Path(__file__).parent / "trace_rows_train.json").read_text())["rows"]]
    runs = tr.whole_runs(rows, "jit_step")[TPU0]
    assert len(runs) == 9                     # 11 recorded, the two at the ends clipped
    assert all(0.3040 < (e - s) / 1e9 < 0.3045 for s, e in runs)
    r = tr.reduce(rows)
    assert 2.9 < r["window_s"] < 3.0 and 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert r["device_ops"][0][0] == "fusion bf16[64,1,512,1,12,64]"
