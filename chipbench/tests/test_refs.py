"""A configuration's reference module, found by the path it names: the
dense module refuses a model it does not compute, and the check and the
MFU readers reach whatever module the configuration names (a stub
here, ``stub_ref.py``) with no edit to the harness."""
import dataclasses
from pathlib import Path

import jax
import pytest

import run
import tiny
import xtrace
from refs import dense

SEED = 2 ** 35 + 3
MODEL = {**tiny.MODEL, "norm_eps": 1e-6}
VIEW = xtrace.TraceView.read(
    str(Path(__file__).parent / "data" / "olmo-1b.decode.view.json.gz"))


@pytest.mark.parametrize("extra", [{"moe": True}, {"n_experts": 64},
                                   {"sliding_window_size": 1024}])
def test_dense_refuses_unknown_keys(extra):
    m = {**MODEL, **extra}
    with pytest.raises(ValueError, match=next(iter(extra))):
        dense.logits(None, m, [1, 2, 3], [2])
    with pytest.raises(ValueError, match=next(iter(extra))):
        dense.prefill_flops(m, 8)
    with pytest.raises(ValueError, match=next(iter(extra))):
        dense.decode_step_flops(m, 8)


@pytest.mark.parametrize("family", ["moe", None])
def test_dense_refuses_other_family(family):
    m = {**MODEL, "family": family}
    for f in (lambda: dense.logits(None, m, [1, 2, 3], [2]),
              lambda: dense.prefill_flops(m, 8),
              lambda: dense.decode_step_flops(m, 8)):
        with pytest.raises(ValueError, match="family"):
            f()


def test_reference_outside_the_benchmark_is_refused(tmp_path):
    outside = tmp_path / "ref.py"
    outside.write_text("CONTROLS = ()\n")
    for rel in ("../run.py", "refs/dense.json", str(outside)):
        with pytest.raises(SystemExit):
            run.load_reference(rel)


def test_check_and_readers_go_through_the_named_module(monkeypatch):
    spec = tiny.spec(reference="tests/stub_ref.py")
    stub = spec["reference"]
    assert stub.logits is not dense.logits and not stub.calls
    seen = []
    read = run.load_reader

    def spy(name):
        def f(r):
            seen.append(r)
            return read(name)(r)
        return f

    monkeypatch.setattr(run, "load_reader", spy)
    res = run.run_cell(spec, seed=SEED, seconds=1e-3, trace=False,
                       devices=jax.devices(), peak=tiny.PEAK)
    # the check drew its logits from the stub: one call per checked
    # request, no control
    assert res["correct"], res["checks"]
    assert stub.calls == [""] * spec["cell"]["check_requests"]
    # the readers see the stub as the run's reference, and the MFU
    # readers take their counts from it
    r = dataclasses.replace(seen[0], trace=VIEW)
    assert r.ref is stub
    s, peak = r.shape, tiny.PEAK["bf16_flops_per_s"]
    period = xtrace.decode_period_s(VIEW)
    assert read("mfu.decode")(r) == pytest.approx(
        100 * min(s.slots, s.requests_per_job) * stub.DECODE_STEP_FLOPS
        / period / peak)
    assert read("mfu.prefill")(r) == pytest.approx(
        100 * r.rate("requests") * stub.PREFILL_FLOPS / peak)
