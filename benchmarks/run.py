"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Select suites with
``python -m benchmarks.run [suite ...]``; default runs all.

``--json-dir DIR`` additionally writes one ``BENCH_<suite>.json``
artifact per suite (rows + metadata) so successive PRs accumulate a
perf trajectory — CI runs ``--json-dir results/bench kernel`` to track
dense-grid vs compacted-grid kernel timings and fetch bytes.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import sys
import time


def _suite_artifact(suite: str, rows) -> dict:
    import jax
    return {
        "suite": suite,
        "unix_time": time.time(),
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "rows": [{"name": n, "us_per_call": us, "derived": derived}
                 for n, us, derived in rows],
    }


def main() -> None:
    from benchmarks.paper_tables import ALL
    from repro.launch.compile_cache import use_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*", default=[],
                    help=f"suites to run (default all): {sorted(ALL)}")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<suite>.json artifacts here")
    args = ap.parse_args()
    use_compile_cache()
    wanted = args.suites or list(ALL)
    out_dir = pathlib.Path(args.json_dir) if args.json_dir else None
    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    for suite in wanted:
        if suite not in ALL:
            print(f"# unknown suite {suite}; have {sorted(ALL)}",
                  file=sys.stderr)
            continue
        rows = ALL[suite]()
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        if out_dir:
            path = out_dir / f"BENCH_{suite}.json"
            path.write_text(json.dumps(_suite_artifact(suite, rows),
                                       indent=1))
            print(f"# wrote {path}", file=sys.stderr)


if __name__ == '__main__':
    main()
