"""A copy of the benchmark's files under a temporary root, with one toy
configuration and two toy cells added by name only: files under
``bench/`` and entries in ``BENCHMARK.json``."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def make_root(tmp: Path, limits: float = 1e9) -> Path:
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "toy.json", root / "bench/configs/toy.json")
    for mode in ("batch", "poisson"):
        shutil.copy(DATA / f"toy-{mode}.json",
                    root / f"bench/traffic/toy-{mode}.json")
        (root / f"bench/limits/toy.{mode}.json").write_text(json.dumps(
            {"kappa_topk_gap": limits}))
        spec["workloads"].append({"name": f"toy.{mode}", "config": "toy",
                                  "traffic": f"toy-{mode}", "chips": 1,
                                  "why": "toy"})
    spec["configs"].append({"name": "toy", "source": "toy",
                            "file": "bench/configs/toy.json",
                            "reduced": [], "why": "toy"})
    # the batch cell reports answer_tok_s, the open-loop cell the
    # latency metrics, each found by name like any other
    toy_cell = {"answer_tok_s": "toy.batch", "ttft_p90_s": "toy.poisson",
                "itl_p50_s": "toy.poisson", "itl_p95_s": "toy.poisson"}
    have = {m["name"]: m for m in spec["end_to_end"]}
    for name, cell in toy_cell.items():
        if name not in have:
            have[name] = {"name": name, "unit": "s", "better": "lower",
                          "bound": 0.1, "source": "host_clock",
                          "workloads": []}
            spec["end_to_end"].append(have[name])
        have[name]["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
