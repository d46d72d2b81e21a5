"""Generate the EXPERIMENTS.md dry-run table from results/dryrun/*.json.

Usage:  PYTHONPATH=src python -m repro.launch.report
"""
from __future__ import annotations

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[3] / "results"


def dryrun_table() -> str:
    rows = []
    for p in sorted((ROOT / "dryrun").glob("*.json")):
        if "__probe" in p.name:
            continue
        r = json.loads(p.read_text())
        if not r.get("ok"):
            rows.append((r["cell"], "FAIL", "", "", "", ""))
            continue
        mem = r.get("memory", {})
        temp = mem.get("temp_size_in_bytes", 0) / 2 ** 30
        args = mem.get("argument_size_in_bytes", 0) / 2 ** 30
        flops = r.get("cost", {}).get("flops", 0)
        coll = r.get("collectives", {}).get("total_bytes", 0)
        per_kind = r.get("collectives", {}).get("per_kind", {})
        kinds = " ".join(f"{k.split('-')[-1][:4]}:{v:.2g}"
                         for k, v in sorted(per_kind.items()))
        rows.append((r["cell"], f"{r['compile_s']:.0f}s",
                     f"{args:.2f}", f"{temp:.2f}",
                     f"{flops:.3g}", kinds or f"{coll:.3g}"))
    out = ["| cell | compile | args GiB/dev | temp GiB/dev | "
           "HLO flops/dev* | collectives (B/dev*) |",
           "|---|---|---|---|---|---|"]
    for r in rows:
        out.append("| " + " | ".join(str(x) for x in r) + " |")
    out.append("")
    out.append("\\* while-loop bodies counted once by XLA.")
    return "\n".join(out)


if __name__ == "__main__":
    print("### Dry-run\n")
    print(dryrun_table())
