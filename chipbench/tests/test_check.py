"""The check that decides ``correct``, driven through a whole run of a
CPU-sized cell (``tiny.py``), with the harness's look for a chip left
out: it passes the program as it is, and fails both fp8 controls and
every fault planted under the timed path (``faults.py``)."""
import jax
import pytest

import faults
import run
import tiny
import weights

SEED = 2 ** 40 + 17


def _run(monkeypatch=None, fault=None):
    if fault is not None:
        from repro.models import decode as dec
        monkeypatch.setattr(dec, "serve_step",
                            faults.FAULTS[fault](dec.serve_step))
    return run.run_cell(tiny.spec(), seed=SEED, seconds=1e-3, trace=False,
                        devices=jax.devices(), peak=tiny.PEAK)


def test_program_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 4
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(monkeypatch, fault):
    res = _run(monkeypatch, fault)
    assert not res["correct"]
    for name in ("max_logit_gap", "mean_logit_gap"):
        gap = res["checks"][name]
        assert gap["value"] > gap["limit"]


def _failed(checks):
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_caught(seed):
    spec = tiny.spec()
    prog = run.Program(spec["config"], spec["traffic"])
    s = prog.shape
    model = {**spec["config"]["model"], **spec["config"]["program_fixed"]}
    ref = spec["reference"]
    params = weights.make(prog.param_shapes(), weights.seed_word(seed, 0))
    js = weights.seed_word(seed, 2, 0)
    rep = prog.job(params, js, s.requests_per_job, s.gen_len)
    job = run.Job(js, 1.0, s.requests_per_job, 0,
                  int(rep["tokens_generated"]), rep)
    both = [run.token_gaps(ref, prog, params, model, job, r, ref.CONTROLS)
            for _, r in run.sample([job], 4, seed)]
    cell = spec["cell"]
    assert not _failed(run.gap_checks([g for g, _ in both], 0, cell))
    for c in ref.CONTROLS:
        assert _failed(run.gap_checks([x[c] for _, x in both], 0, cell)), c
