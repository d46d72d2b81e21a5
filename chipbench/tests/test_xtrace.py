"""Trace reduction on a hand-made view (every number worked out by
hand) and on a view recorded on a TPU v5e (``data/``)."""
from pathlib import Path

import pytest

import xtrace

MS = 1e6   # ns


def _view():
    # window 0..100 ms; two decode steps (each running the kernel), one
    # prefill, one small eager op, and idle stretches the host explains
    ops = [("while.9", 5 * MS, 41 * MS),
           ("fusion.1", 5 * MS, 12 * MS),
           ("sata_decode_attention.3", 12 * MS, 15 * MS),
           ("fusion.2", 30 * MS, 38 * MS),
           ("sata_decode_attention.3", 38 * MS, 40 * MS),
           ("convolution.7", 50 * MS, 80 * MS),
           ("scatter.1", 90 * MS, 105 * MS)]
    modules = [("jit__lambda_(11)", 5 * MS, 15 * MS),
               ("jit__lambda_(11)", 30 * MS, 40 * MS),
               ("jit__lambda_(12)", 50 * MS, 80 * MS),
               ("jit_scatter(3)", 90 * MS, 105 * MS)]
    host = [("serve", -10 * MS, 200 * MS),
            ("lower_sharding_computation", 41 * MS, 49 * MS),
            ("np.asarray", 15 * MS, 30 * MS)]
    return xtrace.TraceView(0.0, 100 * MS, [ops], [modules], host)


def test_busy_and_idle():
    v = _view()
    # busy: 5-41 (the loop), 50-80, 90-100 (clipped) = 76 ms
    assert v.window_s == pytest.approx(0.1)
    assert xtrace.busy_s(v) == pytest.approx(0.076)
    assert xtrace.idle_share(v) == pytest.approx(24.0)


def test_program_runs_and_kernel():
    dec, pre = xtrace.program_runs(_view())
    assert [m[1] for m in dec] == [5 * MS, 30 * MS]
    assert [m[1] for m in pre] == [50 * MS]      # the scatter ran past t1
    n, secs = xtrace.kernel_time(_view())
    assert n == 2 and secs == pytest.approx(0.005)


def test_breakdown():
    v = _view()
    top = xtrace.top_ops(v, 10)
    # the loop holds fusion.1 and more: it is left out of the ranking
    assert [t[0] for t in top] == ["convolution.7", "scatter.1", "fusion.2",
                                   "fusion.1", "sata_decode_attention.3"]
    assert top[0][1] == pytest.approx(0.03)
    assert top[1][1] == pytest.approx(0.01)      # clipped at the window
    assert top[4][1] == pytest.approx(0.005)
    gaps = xtrace.idle_gaps(v)
    # 80-90 (only the host's serve), 41-50 (lowering covers 8 of 9 ms),
    # 0-5 (serve)
    assert gaps == [["serve", pytest.approx(0.010)],
                    ["lower_sharding_computation", pytest.approx(0.009)],
                    ["serve", pytest.approx(0.005)]]
    assert sum(g[1] for g in gaps) == pytest.approx(0.024)


def test_op_name():
    assert xtrace.op_name("%fusion.171 = bf16[1920]{0} fusion(x)") \
        == "fusion.171"
    assert xtrace.op_name("sata_decode_attention.9") \
        == "sata_decode_attention.9"


def test_round_trip(tmp_path):
    v = _view()
    p = str(tmp_path / "v.json.gz")
    v.save(p)
    w = xtrace.TraceView.read(p)
    assert w == v


RECORDED = Path(__file__).parent / "data" / "olmo-1b.decode.view.json.gz"


def test_recorded_decode_trace():
    """0.6 s of a traced ``olmo-1b.decode`` run on one TPU v5 lite, in
    the decode phase (times rebased to the window's start; Python host
    events left out, as the tracer records none)."""
    import numpy as np
    v = xtrace.TraceView.read(str(RECORDED))
    dec, pre = xtrace.program_runs(v)
    # five whole decode steps, each running the kernel once per layer
    assert len(dec) == 5 and not pre
    n, secs = xtrace.kernel_time(v)
    assert n == 5 * 16
    starts = [m[1] for m in dec]
    period_ms = (starts[-1] - starts[0]) / 4 * 1e-6
    assert 100 < period_ms < 130
    assert all(55e6 < e - s < 65e6 for _, s, e in dec)
    # busy time against a 10 us occupancy mask built straight from ops
    mask = np.zeros(int(v.t1 // 1e4) + 1, bool)
    for _, s, e in v.ops[0]:
        mask[int(max(s, v.t0) // 1e4):int(min(e, v.t1) // 1e4)] = True
    assert xtrace.busy_s(v) == pytest.approx(mask.sum() * 1e-5, rel=1e-2)
    assert 40 < xtrace.idle_share(v) < 60
    top = dict(xtrace.top_ops(v))
    assert max(top, key=top.get).startswith(xtrace.DECODE_KERNEL)
    assert top[max(top, key=top.get)] == pytest.approx(secs)
    gaps = xtrace.idle_gaps(v)
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert gaps[0][0] == "np.asarray(jax.Array)"
