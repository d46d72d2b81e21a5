"""Every cell, configuration and metric named in BENCHMARK.json has its
files, and the configuration files build the program's config."""
import json
from pathlib import Path

import pytest

import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("wl", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load(wl):
    spec = run.load_spec(wl, ROOT)
    assert spec["end_to_end"] and spec["per_layer"]
    assert callable(spec["reference"].logits)
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.load_reader(m["name"]))
    prog = run.Program(spec["config"], spec["traffic"])
    s = prog.shape
    assert s.max_len % s.page == 0 and s.prompt_len < s.max_len
    assert prog.cfg.kv.pool_pages == s.pool_pages
    assert spec["cell"]["max_logit_gap"] > 0
    assert 0 < spec["cell"]["mean_logit_gap"] < spec["cell"]["max_logit_gap"]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_names_its_reference(conf):
    rel = json.loads((ROOT / conf["file"]).read_text())["reference"]
    ref = run.load_reference(rel)
    for name in ("logits", "prefill_flops", "decode_step_flops"):
        assert callable(getattr(ref, name)), name
    assert ref.CONTROLS and all(isinstance(c, str) for c in ref.CONTROLS)


def test_config_without_reference_is_refused(tmp_path):
    bench = {**BENCH, "configs": [{**c, "file": f"{c['name']}.json"}
                                  for c in BENCH["configs"]]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    wl = BENCH["workloads"][0]
    conf = json.loads((ROOT / "chipbench/configs"
                       / f"{wl['config']}.json").read_text())
    del conf["reference"]
    (tmp_path / f"{wl['config']}.json").write_text(json.dumps(conf))
    with pytest.raises(SystemExit, match="reference"):
        run.load_spec(wl["name"], tmp_path)


def test_unknown_config_key_is_refused():
    with pytest.raises(ValueError):
        run._build(run.Shape, {"slots": 1, "no_such_knob": 2})
