"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload olmo-1b.decode --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a model
configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``) and has a file of its own
(``cells/<workload>.json``: trace window, correctness sample and
limit).  The configuration names its reference module, a path under
``chipbench/`` (``"reference": "refs/dense.py"``), which holds the
model's float32 logits, its controls and its FLOP count (the interface
is in ``refs/dense.py``).  Each metric is read by
``metrics/<metric>.py``.  Nothing here branches on a cell,
configuration or metric name, or imports a model by name.

A run:

1. set-up: makes the weights on the device from ``--seed``
   (``weights.py``), then serves one warm-up job of the cell's shape
   (one wave: ``slots`` requests) with a short ``gen_len``, so every
   program and eager operation the window uses is compiled and in the
   persistent compilation cache
   (``.jax_cache/`` in the checkout, every program stored however
   quickly it compiled);
2. window: serves whole jobs back to back, each one ``serve()`` call of
   ``requests_per_job`` requests, timed from call to return, starting
   jobs while less than ``--seconds`` have passed.  Rates are the work
   of all jobs over their summed wall time, so no partial job enters a
   metric.  With ``--trace 1`` a profiler trace covers a stretch of the
   first job (the cell file says which) and the per-layer metrics are
   read; with ``--trace 0`` the end-to-end ones;
3. check: reads the device's peak memory, then runs the float32
   reference that the configuration names (``refs/dense.py`` for the
   dense decoders) over a sample of the finished requests,
   drawn from the seed, and compares every served token's logit with
   the reference's best at its position: the widest and the mean of
   those gaps are each held to a limit of the cell's.

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error
and the result's last key.  Without an accelerator, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

# every program compiled or loaded from the persistent cache records a
# compile event; a load also records a cache hit.  The difference is the
# compilations that missed the cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the cell, from files
# ---------------------------------------------------------------------------

def load_spec(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """Everything one cell needs, found by the names in
    ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    conf = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    here = root / "chipbench"

    def applies(m):
        return workload in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    config = json.loads((root / conf["file"]).read_text())
    if "reference" not in config:
        raise SystemExit(f"configuration {conf['file']} names no "
                         f"\"reference\" module")
    return {
        "name": workload, "chips": int(wl["chips"]),
        "config": config,
        "reference": load_reference(config["reference"], here),
        "traffic": json.loads(
            (here / "traffic" / f"{wl['traffic']}.json").read_text()),
        "cell": json.loads((here / "cells" / f"{workload}.json").read_text()),
        "end_to_end": e2e, "per_layer": per_layer,
    }


def _load(path: Path, name: str):
    """The Python file at ``path``, loaded as a module named ``name``
    (its non-alphanumeric characters made ``_``)."""
    spec = importlib.util.spec_from_file_location(
        "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return _load(HERE / "metrics" / f"{name}.py",
                 "chipbench_metric_" + name).read


def load_reference(rel: str, here: Path = HERE):
    """The reference module at ``rel``, a path under ``chipbench/``
    (``here``), as a configuration's ``"reference"`` names it."""
    path = (here / rel).resolve()
    if not path.is_relative_to(here.resolve()) or path.suffix != ".py":
        raise SystemExit(f"reference {rel!r} is not a .py file under "
                         f"chipbench/")
    return _load(path, "chipbench_ref_" + rel[:-3])


def peaks_for(kind: str) -> Dict[str, Any]:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def _build(cls, vals: Dict[str, Any]):
    """A (nested) config dataclass from a JSON object; unknown keys are
    an error, so a file cannot set a knob the program does not have."""
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(vals) - set(fields)
    if unknown:
        raise ValueError(f"{cls.__name__} has no field(s) {sorted(unknown)}")
    kw = {}
    for k, v in vals.items():
        d = fields[k].default
        kw[k] = _build(type(d), v) if (isinstance(v, dict)
                                      and dataclasses.is_dataclass(d)) else v
    return cls(**kw)


@dataclasses.dataclass
class Shape:
    """The cell's serving shape, derived from its traffic and page."""
    slots: int
    prompt_len: int
    gen_len: int
    requests_per_job: int
    warmup_gen_len: int
    page: int

    @property
    def pages_per_request(self) -> int:
        return math.ceil((self.prompt_len + self.gen_len) / self.page)

    @property
    def max_len(self) -> int:
        return self.pages_per_request * self.page

    @property
    def pool_pages(self) -> int:
        """Sized to the traffic: every slot's requests at full length,
        plus the overflow page."""
        return self.slots * self.pages_per_request + 1


class Program:
    """The serving program, driven through its users' entry point."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any]):
        sys.path.insert(0, str(ROOT / "src"))
        from repro.launch import serve as serve_mod
        from repro.models import model as mdl
        from repro.models.config import ModelConfig
        self._serve, self._mdl = serve_mod, mdl
        serving = json.loads(json.dumps(config["serving"]))
        page = int(serving["kv"]["page_size"])
        self.shape = Shape(page=page, **traffic)
        serving["kv"]["pool_pages"] = self.shape.pool_pages
        self.cfg = _build(ModelConfig, {**config["model"], **serving})

    def param_shapes(self):
        import jax
        return jax.eval_shape(lambda k: self._mdl.init_params(k, self.cfg),
                              jax.random.PRNGKey(0))

    def job(self, params, seed: int, n_requests: int, gen_len: int):
        """One whole ``serve()`` call; returns its report."""
        s = self.shape
        opts = self._serve.ServeOptions(
            n_requests=n_requests, batch_slots=s.slots, gen_len=gen_len,
            max_len=s.max_len, prompt_len=s.prompt_len)
        return self._serve.serve(self.cfg.name, cfg=self.cfg, params=params,
                                 seed=seed, options=opts)

    def prompts(self, seed: int, n_requests: int):
        """The prompts ``serve(seed=...)`` draws (uniform token ids, one
        row per request, in request order)."""
        import numpy as np
        return np.random.default_rng(seed).integers(
            0, self.cfg.vocab_size, (n_requests, self.shape.prompt_len))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Job:
    seed: int
    wall_s: float
    requests: int
    prompt_tokens: int
    generated_tokens: int
    report: Dict[str, Any]


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    model: Dict[str, Any]
    shape: Shape
    peak: Dict[str, Any]
    setup_s: float
    jobs: List[Job]
    trace: Any = None                     # xtrace.TraceView when traced
    ref: Any = None                       # the configuration's reference

    def rate(self, field: str) -> Optional[float]:
        wall = sum(j.wall_s for j in self.jobs)
        work = sum(getattr(j, field) for j in self.jobs)
        return work / wall if wall > 0 and work > 0 else None


class _Tracer(threading.Thread):
    """Traces ``seconds`` of the run, starting ``start_s`` from now,
    inside a host annotation that marks the window.  Python functions
    are not traced: the host side is the runtime's own annotations
    (dispatch, lowering, transfers), which name the idle gaps."""

    def __init__(self, start_s: float, seconds: float):
        super().__init__(daemon=True)
        self.start_s, self.seconds = start_s, seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.error: Optional[BaseException] = None

    def run(self):
        import jax
        from xtrace import WINDOW
        try:
            time.sleep(self.start_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW):
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:          # reported by the main thread
            self.error = e

    def view(self):
        import glob
        import xtrace
        if self.error is not None:
            raise self.error
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        try:
            return xtrace.load(found[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def sample(jobs: List[Job], n: int, seed: int):
    """``n`` finished (job, request) pairs drawn from the seed."""
    import numpy as np
    import weights
    pairs = [(j, r) for j in jobs for r in range(j.requests)]
    pick = np.random.default_rng(weights.seed_word(seed, 3)).choice(
        len(pairs), size=min(n, len(pairs)), replace=False)
    return [pairs[int(i)] for i in sorted(pick)]


def token_gaps(ref, prog: Program, params, model, job: Job, r: int,
               controls=()):
    """For each token served to request ``r`` of ``job``: the gap by
    which its float32 logit in the reference module ``ref`` lies below
    the reference's best at its position; and for each control named in
    ``controls`` (``ref.CONTROLS``) the same gap for the token that
    control puts first there.  Returns ``(gaps, {control: gaps})``."""
    import numpy as np
    served = np.asarray(job.report["outputs"][r])
    seq = np.concatenate([prog.prompts(job.seed, job.requests)[r],
                          served[:-1]])
    pos = np.arange(prog.shape.prompt_len - 1, len(seq))
    exact = np.asarray(ref.logits(params, model, seq, pos), np.float64)
    best, rows = exact.max(-1), np.arange(len(served))
    out = {}
    for c in controls:
        low = np.asarray(ref.logits(params, model, seq, pos, low=c))
        out[c] = best - exact[rows, low.argmax(-1)]
    return best - exact[rows, served], out


def gap_checks(gaps, failed: int, cell: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers that decide ``correct``, each beside its limit:
    requests that did not finish (exact), and the widest and the mean
    gap over every checked token (``gaps``: one array per request)."""
    import numpy as np
    g = np.concatenate(gaps) if gaps else np.zeros(1)
    return {
        "failed_requests": {"value": failed, "limit": 0},
        "max_logit_gap": {"value": float(g.max()),
                          "limit": cell["max_logit_gap"]},
        "mean_logit_gap": {"value": float(g.mean()),
                           "limit": cell["mean_logit_gap"]},
    }


def run_cell(spec: Dict[str, Any], *, seed: int, seconds: float,
             trace: bool, devices, peak: Dict[str, Any],
             t0: float = _T0) -> Dict[str, Any]:
    """Set up, measure and check one cell; returns the result object
    (the caller prints it)."""
    with _compile_counter() as compiles:
        return _run_cell(spec, seed, seconds, trace, devices, peak, t0,
                         compiles)


@contextlib.contextmanager
def _compile_counter():
    """Counts compile and cache-hit events while ``["window"]`` is
    true."""
    import jax
    compiles = {"window": False, COMPILE_EVENT: 0, CACHE_HIT_EVENT: 0}

    def on_event(event, *_args, **_kw):
        if compiles["window"] and event in compiles:
            compiles[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        jax.monitoring.unregister_event_listener(on_event)


def _run_cell(spec, seed, seconds, trace, devices, peak, t0, compiles):
    import jax
    import weights
    import xtrace

    prog = Program(spec["config"], spec["traffic"])
    shape, cell = prog.shape, spec["cell"]
    model = {**spec["config"]["model"], **spec["config"]["program_fixed"]}

    # --- set-up: weights, then one warm-up job of the cell's shape
    t_dev = time.perf_counter()
    params = weights.make(prog.param_shapes(), weights.seed_word(seed, 0))
    jax.block_until_ready(params)
    t_w = time.perf_counter()
    prog.job(params, weights.seed_word(seed, 1), shape.slots,
             shape.warmup_gen_len)
    setup_s = time.perf_counter() - t0
    say(f"set-up: {setup_s} s (to the device {t_dev - t0} s, weights "
        f"{t_w - t_dev} s, warm-up job {t0 + setup_s - t_w} s)")

    # --- window: whole jobs, back to back
    tracer = _Tracer(cell["trace_start_s"], cell["trace_seconds"]) \
        if trace else None
    jobs: List[Job] = []
    compiles["window"] = True
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        js = weights.seed_word(seed, 2, len(jobs))
        if tracer is not None and not jobs:
            tracer.start()
        t = time.perf_counter()
        rep = prog.job(params, js, shape.requests_per_job, shape.gen_len)
        wall = time.perf_counter() - t
        jobs.append(Job(js, wall, shape.requests_per_job,
                        shape.requests_per_job * shape.prompt_len,
                        int(rep["tokens_generated"]), rep))
    compiles["window"] = False
    if tracer is not None:
        tracer.join()
    say(f"window: {len(jobs)} jobs in {sum(j.wall_s for j in jobs)} s "
        f"(job walls {[j.wall_s for j in jobs]})")
    say(f"window compilations that missed the cache: "
        f"{compiles[COMPILE_EVENT] - compiles[CACHE_HIT_EVENT]} "
        f"(should read 0; {compiles[CACHE_HIT_EVENT]} programs loaded "
        f"from the cache)")

    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices)
    say(f"peak_bytes_in_use: {mem} (cell file estimate: "
        f"{cell.get('memory_estimate_bytes')})")
    ref = spec["reference"]
    run = Run(model, shape, peak, setup_s, jobs,
              tracer.view() if tracer is not None else None, ref)

    # --- check: every request finished, served tokens vs reference
    failed = sum(
        1 for j in jobs for r in range(j.requests)
        if len(j.report["outputs"].get(r, [])) != shape.gen_len
        or r in j.report.get("timed_out", []))
    gaps = [token_gaps(ref, prog, params, model, j, r)[0]
            for j, r in sample(jobs, int(cell["check_requests"]), seed)
            if len(j.report["outputs"].get(r, [])) == shape.gen_len]
    checks = gap_checks(gaps, failed, cell)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        v = load_reader(m["name"])(run)
        if v is None and not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct,
              "attempted": sum(j.requests for j in jobs),
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = xtrace.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": xtrace.top_ops(run.trace),
                               "idle_gaps": xtrace.idle_gaps(run.trace)}
    result["checks"] = checks
    return result


def chips(n: int):
    """Configure JAX (compilation cache in the checkout, every program
    stored; the TPU runtime's log directory off) and return the first
    ``n`` accelerator devices, or None, said on standard error, when
    there are fewer."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < n:
        print(f"[chipbench] needs {n} accelerator chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    devs = devs[:n]
    say(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    devs = chips(spec["chips"])
    if devs is None:
        return 2
    result = run_cell(spec, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devs,
                      peak=peaks_for(devs[0].device_kind))
    for name, c in result["checks"].items():
        print(f"[chipbench] check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
