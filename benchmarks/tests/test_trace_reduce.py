"""trace_reduce.py on a trace recorded on the chip (TPU v5 lite, PR 23:
four steps of the train runner at preset ``tiny``) and on hand-made planes."""

import gzip
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata", "tiny_train.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.reduce_dir(str(path))


def test_recorded_trace_against_numbers_worked_out_by_hand(recorded):
    # host plane: bench.window from 43,971,756 ns lasting 7,044,989 ns
    assert recorded.window_s == pytest.approx(7_044_989e-9, rel=1e-9)
    # the first of the four step programs ran before the window opened (device
    # clock 43,667,929..43,717,417 ns); the other three last 49,326 + 49,552 +
    # 50,058 ns, and the union of the op intervals inside the window is 124,254 ns
    assert len(recorded.devices) == 1
    assert recorded.busy_s == pytest.approx(124_254e-9, rel=1e-6)
    assert recorded.busy_s <= (49_326 + 49_552 + 50_058) * 1e-9
    assert recorded.idle_share == pytest.approx(1 - 124_254 / 7_044_989, rel=1e-6)
    # %fusion.1 (kind=kCustom, bf16[256,64]) runs once a step: 1145 + 1143 + 1143 ns
    ops = dict(recorded.top_ops(2000))
    assert ops["fusion.1 kCustom bf16[256,64]"] == pytest.approx(3431e-9, rel=1e-6)
    assert recorded.op_seconds("%fusion.1 =") == pytest.approx(3431e-9, rel=1e-6)
    # while loops only contain their bodies' ops: no time of their own
    assert not any(k.startswith("while") for k in ops)
    # almost all of this tiny step is the host dispatching it
    (label, seconds), *_ = recorded.top_gaps(3)
    assert label == "bench.step_dispatch" and seconds > 0.9 * recorded.window_s * 0.98
    assert recorded.devices[0].collective_s == 0.0


def test_parse_op_reads_the_hlo_text():
    text = ("%fusion.274 = f32[8]{0:T(128)S(1)} fusion(), kind=kLoop, "
            "calls=%fused_computation.385")
    assert tr.parse_op(text) == ("fusion.274", "fusion", "fusion.274 kLoop f32[8]")
    text = ("%while.9 = (s32[]{:T(128)}, bf16[3,64,64]{2,1,0:T(8,128)(2,1)S(1)}) "
            "while((s32[]{:T(128)}, bf16[3,64,64]{2,1,0}) %tuple.1), condition=%c, body=%b")
    assert tr.parse_op(text)[:2] == ("while.9", "while")
    text = ('%custom-call.3 = bf16[2,3,64,64]{2,3,1,0} custom-call(), '
            'custom_call_target="AllocateBuffer"')
    short, opcode, label = tr.parse_op(text)
    assert (short, opcode) == ("custom-call.3", "custom-call")
    assert label.endswith("AllocateBuffer")
    assert tr.parse_op("%all-gather-start.2 = (f32[4]{0}) all-gather-start(f32[1]{0} %p)")[1] \
        == "all-gather-start"
    assert tr.is_collective("all-gather-start") and not tr.is_collective("fusion")


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def test_busy_union_exposed_collectives_and_gap_labels_on_made_up_planes():
    ops = NS(name="XLA Ops", events=[
        ev("%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b", 100, 300),
        ev("%fusion.1 = f32[8]{0} fusion(), kind=kLoop", 100, 100),    # 100..200
        ev("%fusion.2 = f32[8]{0} fusion(), kind=kOutput", 150, 150),  # 150..300 overlaps
        ev("%all-gather-start.1 = (f32[8]{0}) all-gather-start(f32[2]{0} %p)", 300, 1),
        ev("%fusion.3 = f32[8]{0} fusion(), kind=kLoop", 320, 30),     # 320..350
        ev("%all-gather-done.1 = f32[8]{0} all-gather-done((f32[8]{0}) %s)", 350, 50),
        ev("%fusion.1 = f32[8]{0} fusion(), kind=kLoop", 600, 100),    # 600..700
    ])
    asyn = NS(name="Async XLA Ops", events=[
        ev("%all-gather-start.1 = (f32[8]{0}) all-gather-start(f32[2]{0} %p)", 300, 100),
    ])
    host = NS(name="python3", events=[
        ev("bench.window", 0, 1000),
        ev("bench.data_next", 400, 150),      # covers most of the gap 400..600
        ev("bench.step_wait", 550, 200),
    ])
    planes = [NS(name="/device:TPU:0", lines=[ops, asyn]),
              NS(name="/host:CPU", lines=[host])]
    r = tr.reduce_planes(planes)
    assert r.window_s == pytest.approx(1000e-6)
    # busy: 100..400 (ops and the waiting done) and 600..700
    assert r.busy_s == pytest.approx(400e-6)
    assert r.idle_share == pytest.approx(0.6)
    d = r.devices[0]
    assert d.ops["fusion.1 kLoop f32[8]"] == pytest.approx(200e-6)
    # the all-gather spans 300..400; fusion.3 hides 320..350 of it
    assert d.collective_s == pytest.approx(100e-6)
    assert d.collective_exposed_s == pytest.approx(70e-6)
    gaps = dict(r.top_gaps(5))
    assert gaps["bench.data_next"] == pytest.approx(200e-6)   # 400..600
    assert gaps["(no annotation)"] == pytest.approx(100e-6)   # 0..100
    assert gaps["bench.step_wait"] == pytest.approx(300e-6)   # 700..1000


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 2), (1, 2.5), (4, 5)]) == [(0, 2.5), (3, 5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.total(tr.clip([(0, 5), (8, 12)], 2, 10)) == 5
    with pytest.raises(ValueError):
        tr.reduce_planes([NS(name="/host:CPU", lines=[])])
