"""Readings that the correctness limits of a cell are set from.  Not
part of a benchmark run.

    python3 chipbench/calibrate.py --workload olmo-1b.decode \
        --seeds 11,12,13 --control-seeds 3

For each seed, in one process on the chip: the weights from the seed,
the first jobs a run with that seed would serve (as many as its check
sample needs), and, over the requests such a run would check, the gap
by which each served token's logit lies below the float32 reference's
best (the program), and the same gap for the token each control of the
configuration's reference module (its ``"reference"``, e.g.
``refs/dense.py``) puts first at that position (on the first
``--control-seeds`` seeds).  Of each: the widest gap (``max``), the
mean, the 90th percentile and the share of tokens that are not the
reference's best, with the first token's (the prefill's) widest gap
apart.  One JSON line per seed, then the largest program reading and
the smallest control reading of each statistic.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import run


def stats(g):
    """Statistics of gaps ``g`` (requests × tokens)."""
    import numpy as np
    return {"max": float(g.max()), "mean": float(g.mean()),
            "p90": float(np.quantile(g, 0.9)),
            "miss_share": float((g > 0).mean()),
            "first_max": float(g[:, 0].max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the controls on the first N seeds only")
    args = ap.parse_args(argv)
    spec = run.load_spec(args.workload)
    if run.chips(spec["chips"]) is None:
        return 2
    import numpy as np
    import weights
    ref = spec["reference"]
    prog = run.Program(spec["config"], spec["traffic"])
    s = prog.shape
    model = {**spec["config"]["model"], **spec["config"]["program_fixed"]}
    n_check = int(spec["cell"]["check_requests"])
    n_jobs = math.ceil(n_check / s.requests_per_job)
    shapes = prog.param_shapes()
    seeds = [int(x) for x in args.seeds.split(",")]
    n_ctl = len(seeds) if args.control_seeds is None else args.control_seeds
    rows = []
    for i, seed in enumerate(seeds):
        params = weights.make(shapes, weights.seed_word(seed, 0))
        if i == 0:
            prog.job(params, weights.seed_word(seed, 1), s.slots,
                     s.warmup_gen_len)
        jobs = []
        for n in range(n_jobs):
            js = weights.seed_word(seed, 2, n)
            t = time.perf_counter()
            rep = prog.job(params, js, s.requests_per_job, s.gen_len)
            jobs.append(run.Job(js, time.perf_counter() - t,
                                s.requests_per_job,
                                s.requests_per_job * s.prompt_len,
                                int(rep["tokens_generated"]), rep))
        controls = ref.CONTROLS if i < n_ctl else ()
        t = time.perf_counter()
        both = [run.token_gaps(ref, prog, params, model, j, r, controls)
                for j, r in run.sample(jobs, n_check, seed)]
        row = {"seed": seed,
               "program": stats(np.stack([b[0] for b in both]))}
        for c in controls:
            row[c] = stats(np.stack([b[1][c] for b in both]))
        row.update(job_s=[j.wall_s for j in jobs],
                   check_s=time.perf_counter() - t,
                   tokens=sum(len(b[0]) for b in both))
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_max": {k: max(r["program"][k] for r in rows)
                               for k in rows[0]["program"]}}
    for c in ref.CONTROLS:
        got = [r[c] for r in rows if c in r]
        if got:
            summary[c + "_min"] = {k: min(g[k] for g in got) for k in got[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
