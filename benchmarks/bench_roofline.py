"""Beyond-paper: roofline table of the model dry-run artifacts.

Reads results/dryrun_singlepod.json (produced by repro.launch.dryrun) and
prints the per-(arch × shape) three-term roofline — no recompilation.
"""

from __future__ import annotations

import json
import os

from . import common

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "dryrun_singlepod.json")


def run(quick: bool = False, path: str = RESULTS):
    rows = []
    if not os.path.exists(path):
        print(f"bench_roofline: {path} not found — run "
              "`python -m repro.launch.dryrun --mesh single --out "
              "results/dryrun_singlepod.json` first")
    else:
        for cell in json.load(open(path)):
            if "roofline" not in cell:
                continue
            rl = cell["roofline"]
            rows.append({
                "arch": cell["arch"], "shape": cell["shape"],
                "t_compute_ms": rl["t_compute_s"] * 1e3,
                "t_memory_ms": rl["t_memory_s"] * 1e3,
                "t_collective_ms": rl["t_collective_s"] * 1e3,
                "bottleneck": rl["bottleneck"],
                "useful_ratio": rl["useful_ratio"],
                "roofline_fraction": rl["roofline_fraction"],
            })
        common.print_rows("bench_roofline (dry-run derived)", rows)
    return {"name": "roofline", "cells": len(rows)}


if __name__ == "__main__":
    run()
