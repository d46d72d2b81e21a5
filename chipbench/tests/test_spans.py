"""The serve-loop readers (``spans.py``) on a hand-made view with
``serve.*`` host spans and on hand-made job reports, every number worked
out by hand, and on a program without spans, where they read nothing."""
from pathlib import Path

import pytest

import run
import spans
import xtrace

MS = 1e6   # ns


def _view(with_spans=True):
    # three decode steps starting at 10, 40 and 70 ms: the stretch read is
    # 10-70 ms, two periods of 30 ms.  Busy in it: 10-25, 30-31 (a token
    # slice), 40-52 = 28 ms; idle 25-30, 31-40, 52-70 = 32 ms
    ops = [("fusion.1", 10 * MS, 20 * MS),
           ("sata_decode_attention.3", 20 * MS, 25 * MS),
           ("slice.4", 30 * MS, 31 * MS),
           ("fusion.1", 40 * MS, 48 * MS),
           ("sata_decode_attention.3", 48 * MS, 52 * MS),
           ("fusion.1", 70 * MS, 80 * MS),
           ("sata_decode_attention.3", 80 * MS, 85 * MS)]
    modules = [("jit__lambda_(11)", 10 * MS, 25 * MS),
               ("jit__lambda_(11)", 40 * MS, 52 * MS),
               ("jit__lambda_(11)", 70 * MS, 85 * MS)]
    host = [("np.asarray(jax.Array)", 10 * MS, 26 * MS)]
    if with_spans:
        host += [(n, s * MS, e * MS) for n, s, e in [
            ("serve.step", 5, 38),
            ("serve.decode", 5, 10),
            ("serve.account", 10, 28),
            ("serve.pull", 10, 26), ("serve.pull", 26, 27),
            ("serve.emit", 28, 34),
            ("serve.pull", 29, 31), ("serve.pull", 32, 33),
            ("serve.tables", 34, 37),            # 37-38: the step alone
            ("serve.step", 38, 66),
            ("serve.decode", 38, 40),
            ("serve.account", 40, 55), ("serve.pull", 40, 54),
            ("serve.emit", 55, 62), ("serve.pull", 56, 58),
            ("serve.tables", 62, 65),            # 65-66: the step alone
            ("serve.step", 68, 100),             # 66-68: no span
            ("serve.decode", 68, 70)]]
    return xtrace.TraceView(0.0, 100 * MS, [ops], [modules], host)


def test_innermost():
    sp = [("serve.step", 0, 10), ("serve.pull", 2, 4),
          ("serve.emit", 2, 6), ("serve.step", 12, 20)]
    # at 2 the emit and the pull both start: the shorter one is inside
    assert spans.innermost(sp, 1, 14) == [
        (1, 2, "serve.step"), (2, 4, "serve.pull"), (4, 6, "serve.emit"),
        (6, 10, "serve.step"), (10, 12, None), (12, 14, "serve.step")]


def test_idle_split():
    got = spans.idle_split(_view())
    # idle 25-30: pulls 25-27 and 29-30, account 27-28, emit 28-29;
    # 31-40: emit 31-32, 33-34, pull 32-33, tables 34-37, step 37-38,
    # decode 38-40; 52-70: pulls 52-54 and 56-58, account 54-55, emit
    # 55-56 and 58-62, tables 62-65, step 65-66, nothing 66-68, decode
    # 68-70.  Pull 8 ms, host 22, unattributed 2: over two steps
    assert got["pull"] == pytest.approx(4.0)
    assert got["host"] == pytest.approx(11.0)
    assert got["unattributed"] == pytest.approx(1.0)
    assert got["busy"] == pytest.approx(14.0)
    assert got["period"] == pytest.approx(30.0)
    assert got["pull"] + got["host"] + got["unattributed"] + got["busy"] \
        == pytest.approx(got["period"])
    by_name = {k: got[k] for k in ("serve.account", "serve.emit",
                                   "serve.tables", "serve.step",
                                   "serve.decode")}
    assert by_name == pytest.approx({"serve.account": 1.0, "serve.emit": 4.0,
                                     "serve.tables": 3.0, "serve.step": 1.0,
                                     "serve.decode": 2.0})
    assert sum(by_name.values()) == pytest.approx(got["host"])
    # the window's idle share agrees: 32 of the stretch's 60 ms idle
    idle = got["pull"] + got["host"] + got["unattributed"]
    assert 2 * idle == pytest.approx(32.0)


def test_readers_on_the_trace():
    r = run.Run({}, None, {}, 0.0, [], _view())
    assert run.load_reader("host_step_ms")(r) == pytest.approx(11.0)
    assert run.load_reader("pull_ms_per_step")(r) == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["host_step_ms", "pull_ms_per_step"])
def test_trace_readers_without_spans(name):
    """A program without spans, or a run not traced, reads nothing."""
    assert spans.idle_split(_view(with_spans=False)) is None
    assert run.load_reader(name)(
        run.Run({}, None, {}, 0.0, [], _view(with_spans=False))) is None
    assert run.load_reader(name)(run.Run({}, None, {}, 0.0, [], None)) \
        is None


def _job(report):
    return run.Job(1, 10.0, 2, 20, 8, report)


def _reports():
    # two jobs: 4 + 6 steps, 10 + 14 reads; queue waits 0, 5, 20 and
    # 1, 2 ms; set-up 300 and 500 ms
    a = {"steps": 4, "pulls": 10, "queue_wait_s": {0: 0.0, 1: 0.005,
                                                  2: 0.020},
         "spans": {"serve.setup": {"count": 1, "total_s": 0.3,
                                   "max_s": 0.3}}}
    b = {"steps": 6, "pulls": 14, "queue_wait_s": {0: 0.001, 1: 0.002},
         "spans": {"serve.setup": {"count": 1, "total_s": 0.5,
                                   "max_s": 0.5}}}
    return [_job(a), _job(b)]


def test_counter_readers():
    r = run.Run({}, None, {}, 0.0, _reports(), None)
    assert run.load_reader("pulls_per_step")(r) == pytest.approx(24 / 10)
    for cell in ("decode", "prefill"):
        assert run.load_reader(f"queue_wait_ms.{cell}")(r) \
            == pytest.approx(28 / 5)
        assert run.load_reader(f"call_setup_ms.{cell}")(r) \
            == pytest.approx(400.0)


@pytest.mark.parametrize("name", ["pulls_per_step", "queue_wait_ms.decode",
                                  "queue_wait_ms.prefill",
                                  "call_setup_ms.decode",
                                  "call_setup_ms.prefill"])
def test_counter_readers_without_counters(name):
    """Reports of a program without the counters read nothing."""
    jobs = [_job({"steps": 4, "tokens_generated": 8})] * 2
    assert run.load_reader(name)(run.Run({}, None, {}, 0.0, jobs, None)) \
        is None


RECORDED = (Path(__file__).parent / "data"
            / "qwen3-4b.decode.spans.view.json.gz")


def test_recorded_decode_trace_with_spans():
    """0.3 s of a traced ``qwen3-4b.decode`` run on one TPU v5 lite, in
    the decode phase, with ``serve()``'s host spans (times rebased to the
    window's start; every host event kept)."""
    v = xtrace.TraceView.read(str(RECORDED))
    dec, pre = xtrace.program_runs(v)
    assert len(dec) == 5 and not pre
    got = spans.idle_split(v)
    period = xtrace.decode_period_s(v) * 1e3
    assert 55 < period < 70
    assert got["period"] == pytest.approx(period)
    idle = got["pull"] + got["host"] + got["unattributed"]
    assert idle + got["busy"] == pytest.approx(period)
    # the spans cover the idle time, and the token reads lead it
    assert got["pull"] + got["host"] >= 0.9 * idle
    assert got["pull"] > 2 * got["host"]
    # mid-decode every slot is live: a step reads the plan twice and
    # each of the 8 slots' tokens twice
    inside = [e for e in v.host if e[1] >= v.t0 and e[2] <= v.t1]
    steps = [e for e in inside if e[0] == "serve.step"]
    assert steps
    for s in steps:
        pulls = [e for e in inside if e[0] == spans.PULL
                 and s[1] <= e[1] and e[2] <= s[2]]
        assert len(pulls) == 18
