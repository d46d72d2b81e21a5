"""Each metric reader on a run assembled from the recorded trace and
``serve()`` report counts of the size an ``olmo-1b.decode`` job has."""
import json
from pathlib import Path

import pytest

import run
import xtrace

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "chipbench/configs/olmo-1b.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "chipbench/traffic/decode-s16-p1536-g128.json").read_text())
PEAK = json.loads((ROOT / "chipbench/peaks.json").read_text())["TPU v5 lite"]
VIEW = xtrace.TraceView.read(
    str(Path(__file__).parent / "data" / "olmo-1b.decode.view.json.gz"))

STEPS, LAYERS = 127, 16
TILES_PER_CALL = 16 * 16 * 12          # every slot's 12 blocks, each head


def _run(trace=VIEW, wall=20.0):
    report = {"steps": STEPS, "tokens_generated": 16 * 128,
              "decode_fetch": {
                  "kv_fetch_tiles_plan": TILES_PER_CALL * STEPS * LAYERS,
                  "kv_fetch_tiles_dense": TILES_PER_CALL * STEPS * LAYERS
                  * 10 // 9}}
    job = run.Job(1, wall, 16, 16 * 1536, 16 * 128, report)
    shape = run.Shape(page=128, **TRAFFIC)
    model = {**CONFIG["model"], **CONFIG["program_fixed"]}
    return run.Run(model, shape, PEAK, 30.0, [job, job], trace,
                   run.load_reference(CONFIG["reference"]))


def test_rates():
    r = _run()
    assert run.load_reader("out_tok_s")(r) == pytest.approx(2048 / 20)
    assert run.load_reader("prompt_tok_s")(r) == pytest.approx(
        16 * 1536 / 20)
    assert run.load_reader("setup_s")(r) == 30.0
    assert run.load_reader("plan_tile_share")(r) == pytest.approx(90.0,
                                                                  rel=1e-3)


def test_trace_readers():
    r = _run()
    assert 100 < run.load_reader("decode_step_ms")(r) < 130
    idle = run.load_reader("idle_share.decode")(r)
    assert idle == pytest.approx(xtrace.idle_share(VIEW))
    roof = run.load_reader("decode_kernel_roofline")(r)
    # 3072 tiles of 64 KiB in ~1.38 ms per call at 819 GB/s: about 18%
    assert 10 < roof < 25
    assert run.load_reader("prefill_ms")(r) is None     # no prefill here


def test_mfu_below_peak():
    from refs import dense
    r = _run()
    mfu = run.load_reader("mfu.decode")(r)
    assert 0 < mfu < 100
    # 16 busy slots, each one decode step per period; positions 1536-1662
    per = sum(dense.decode_step_flops(r.model, p)
              for p in range(1536, 1663)) / 127
    period = run.load_reader("decode_step_ms")(r) * 1e-3
    assert mfu == pytest.approx(16 * per / period
                                / PEAK["bf16_flops_per_s"] * 100)
    # prefill: 32 requests over 40 s, one prompt's prefill each
    pre = run.load_reader("mfu.prefill")(r)
    assert pre == pytest.approx(32 / 40 * dense.prefill_flops(r.model, 1536)
                                / PEAK["bf16_flops_per_s"] * 100)


def test_untraced_readers_read_nothing():
    r = _run(trace=None)
    for name in ("decode_step_ms", "mfu.decode", "decode_kernel_roofline",
                 "prefill_ms",
                 "idle_share.decode", "idle_share.prefill"):
        assert run.load_reader(name)(r) is None
