"""The reduction from a trace to numbers: first on intervals worked by
hand, then on a small piece cut out of a trace recorded on the chip
(``data/small_trace.json``: device 0 of a decode-batch run, PR 22)."""
import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_subtract_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]


def _hand_made():
    dev = "/device:TPU:0"
    return {
        dev: {
            tr.MODULES_LINE: [("jit_step(1)", 10, 50), ("jit_step(1)", 60, 90),
                              ("jit_prefill(2)", 100, 120)],
            tr.OPS_LINE: [
                ("%fusion.1 = f32[8] fusion(f32[8] %p)", 10, 30),
                ("%all-reduce.1 = f32[8] all-reduce(f32[8] %fusion.1)", 30, 50),
                # overlaps the collective, and only READS one
                ("%fusion.2 = f32[8] fusion(f32[8] %all-reduce.1)", 40, 45),
                ("%fusion.1 = f32[8] fusion(f32[8] %p)", 60, 90),
                ("%kernel.3 = f32[8] custom-call(f32[8] %p)", 100, 120),
                # reads a kernel's result, is none itself
                ("%fusion.4 = f32[8] fusion(f32[8] %custom-call.7)", 120, 121),
                ("%fusion.9 = f32[8] fusion()", 500, 600)],   # outside
            # an asynchronous all-gather under way 85..100: 85..90 hidden
            # behind fusion.1, 90..100 exposed
            tr.ASYNC_LINE: [("%all-gather-start.2 = f32[8] all-gather-start()",
                             85, 100)],
        },
        "/device:TPU:1": {tr.OPS_LINE: [("fusion.1", 0, 100)]},
        tr.HOST_PLANE: {
            "main": [("bench:slice", 0, 200), ("bench:pipeline.next", 50, 60),
                     ("bench:trainer.step", 90, 100), ("other", 0, 500)],
        },
    }


def test_busy_idle_modules_collectives_by_hand():
    t = tr.Trace(_hand_made())
    assert t.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert t.window == (0, 200)
    assert t.busy(t.devices[0]) == [(10, 50), (60, 90), (100, 121)]
    # device 0 busy 91 ns, device 1 busy 100 ns, averaged
    assert t.busy_s() == pytest.approx(95.5e-9)
    assert t.window_s == pytest.approx(200e-9)
    assert t.module_s() == pytest.approx(90e-9)
    assert t.module_s("prefill") == pytest.approx(20e-9)
    assert sorted(t.module_durations_s("step")) == \
        pytest.approx([30e-9, 40e-9])
    assert t.op_s(tr.MOSAIC) == pytest.approx(20e-9)
    # the all-reduce ran 30..50 with a fusion over 40..45 of it; the
    # all-gather was exposed 90..100
    assert t.exposed_collective_s() == pytest.approx(25e-9)
    top = t.top_ops(2)
    assert top[0][0].startswith("%fusion.1 =") \
        and top[0][1] == pytest.approx(50e-9)
    assert top[1][0].startswith("%all-reduce.1 =") \
        and top[1][1] == pytest.approx(20e-9)
    gaps = dict(t.idle_gaps(unnamed="scheduler"))
    assert gaps["pipeline.next"] == pytest.approx(10e-9)   # 50..60
    assert gaps["trainer.step"] == pytest.approx(10e-9)    # 90..100
    assert gaps["scheduler"] == pytest.approx(89e-9)       # 0..10, 121..200


def test_window_falls_back_to_the_device_events():
    planes = _hand_made()
    del planes[tr.HOST_PLANE]
    t = tr.Trace(planes)
    assert t.window == (0, 600)
    assert t.idle_gaps(unnamed="x")[0][0] == "x"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        planes = json.load(f)["planes"]
    return tr.Trace({p: {l: [tuple(e) for e in evs]
                         for l, evs in lines.items()}
                     for p, lines in planes.items()})


def test_recorded_trace_has_the_lines_the_reduction_reads(recorded):
    dev = recorded.devices[0]
    assert tr.DEVICE_PLANE.match(dev)
    assert recorded.events(dev, tr.MODULES_LINE)
    assert recorded.events(dev, tr.OPS_LINE)


def test_recorded_trace_reduces_consistently(recorded):
    dev = recorded.devices[0]
    busy = tr.total(recorded.busy(dev)) / 1e9
    window = recorded.window_s
    assert 0 < busy <= window
    idle = sum(s for _, s in recorded.idle_gaps(n=10 ** 6))
    assert busy + idle == pytest.approx(window, rel=1e-6)
    # programs cover their operations: module time >= op time, and both
    # are what the union of op intervals gives
    assert recorded.module_s() >= busy * 0.999
    step = recorded.module_durations_s("_decode_fn")
    assert step and all(0.001 < d < 0.1 for d in step)
    assert 0 < recorded.op_s(tr.MOSAIC) < busy
    assert sum(s for _, s in recorded.top_ops(n=10 ** 6)) >= busy * 0.999
