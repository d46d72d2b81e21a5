"""Host spans and counters of the serving loop (``launch/spans.py``):
the report's totals and counts, the profiler events they leave on the
host plane, and proof that they compute nothing."""
import dataclasses
import glob
import time

import jax
import pytest

from repro.configs.archs import SMOKE
from repro.launch import spans as spans_mod
from repro.launch.serve import ServeOptions, serve
from repro.models.config import KVCacheConfig, SataConfig, SataDecodeConfig

N_REQ, SLOTS, GEN, PROMPT = 3, 2, 6, 5
NAMES = ("serve.setup", "serve.step", "serve.prefill", "serve.tables",
         "serve.decode", "serve.account", "serve.emit", "serve.pull")


def _serve():
    cfg = dataclasses.replace(
        SMOKE["qwen3-4b"], topk_impl="bisect", kv=KVCacheConfig("paged"),
        sata=SataConfig(decode=SataDecodeConfig(mode="on", block=8,
                                                replan=4)))
    return serve("qwen3-4b", cfg=cfg, options=ServeOptions(
        n_requests=N_REQ, batch_slots=SLOTS, gen_len=GEN, max_len=32,
        prompt_len=PROMPT))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One paged SATA serve under the profiler: (report, host events
    named ``serve.*`` as (name, start_ns, end_ns), by start)."""
    from jax.profiler import ProfileData
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        out = _serve()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(d + "/**/*.xplane.pb", recursive=True)[0])
    evs = sorted(((e.name, e.start_ns, e.end_ns)
                  for p in pd.planes if p.name.startswith("/host:")
                  for line in p.lines for e in line.events
                  if e.name.startswith("serve.")), key=lambda e: e[1])
    return out, evs


def test_report_spans_and_counters(traced):
    out, _ = traced
    sp, steps = out["spans"], out["steps"]
    assert set(sp) == set(NAMES)
    for name in ("serve.step", "serve.tables", "serve.decode",
                 "serve.account", "serve.emit"):
        assert sp[name]["count"] == steps, name
    assert sp["serve.setup"]["count"] == 1
    assert sp["serve.prefill"]["count"] == N_REQ
    for s in sp.values():
        assert 0 < s["max_s"] <= s["total_s"]
    # the children of a step take no more than the steps themselves
    kids = sum(sp[n]["total_s"] for n in NAMES[2:7])
    assert kids <= sp["serve.step"]["total_s"]
    # reads: each prefill's first token, then one a step (its tokens
    # and plan counters in one transfer)
    assert out["pulls"] == N_REQ + steps
    assert sp["serve.pull"]["count"] == out["pulls"]
    # queue wait: one entry a request, non-negative, in claim order
    qw = out["queue_wait_s"]
    assert sorted(qw) == list(range(N_REQ))
    waits = [qw[r] for r in range(N_REQ)]          # FIFO claims
    assert min(waits) >= 0 and waits == sorted(waits)
    assert set(out["request_latency_s"]) == set(qw)


def test_spans_compute_nothing(traced, monkeypatch):
    """Outputs are bitwise those of the same call with every span a
    no-op."""
    class _Noop:
        def open(self):
            return self

        def close(self):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(spans_mod.Spans, "__call__",
                        lambda self, name, **meta: _Noop())
    monkeypatch.setattr(spans_mod.Spans, "step",
                        lambda self, name, step_num: _Noop())
    plain = _serve()
    assert plain["spans"] == {}
    assert plain["outputs"] == traced[0]["outputs"]
    assert plain["steps"] == traced[0]["steps"]
    assert plain["pulls"] == traced[0]["pulls"]


def test_profiler_host_plane(traced):
    out, evs = traced
    setup = [e for e in evs if e[0] == "serve.setup"]
    steps = [e for e in evs if e[0] == "serve.step"]
    pulls = [e for e in evs if e[0] == "serve.pull"]
    assert len(setup) == 1 and len(steps) == out["steps"]
    assert all(s[1] >= setup[0][2] for s in steps)
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))
    assert len(pulls) == out["pulls"]
    claims = [e for e in evs if e[0] == "serve.prefill"]
    for s in steps:
        inside = [p for p in pulls if s[1] <= p[1] and p[2] <= s[2]
                  and not any(c[1] <= p[1] and p[2] <= c[2]
                              for c in claims)]
        assert len(inside) == 1      # the step's read; claims read apart
    # every pull lies inside a step
    assert all(any(s[1] <= p[1] and p[2] <= s[2] for s in steps)
               for p in pulls)


@pytest.mark.parametrize("nest", [False, True])
def test_span_totals(nest):
    sp = spans_mod.Spans()
    for _ in range(3):
        with sp("outer"):
            time.sleep(0.002)
            if nest:
                with sp("inner", request=1):
                    time.sleep(0.001)
    handle = sp.step("loop", 0).open()
    handle.close()
    rep = sp.report()
    assert rep["outer"]["count"] == 3 and rep["loop"]["count"] == 1
    assert rep["outer"]["total_s"] >= 3 * 0.002
    assert rep["outer"]["max_s"] <= rep["outer"]["total_s"]
    if nest:
        assert rep["inner"]["count"] == 3
        assert rep["inner"]["total_s"] < rep["outer"]["total_s"]
    else:
        assert "inner" not in rep
