#!/usr/bin/env python3
"""Read the control of a configuration's PageRank limit on the chip.

    python bench/control.py --config g500-s20 --seeds 11 12 13 [--queries 4]

For each seed: the configuration's graph made as a run makes it, dampings
drawn as the window draws them, the bfloat16 control
(``harness.control``) against the float64 reference. Prints one JSON
object: every reading, the smallest, and the configuration's limit. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=4, help="PageRank queries per seed")
    args = ap.parse_args(argv)

    import jax

    from harness import reference
    from harness.control import control_errors
    from harness.kronecker import kronecker_edges, vertex_map
    from harness.traffic import QueryStream

    with open(BENCH_DIR / "configs" / f"{args.config}.json") as f:
        config = json.load(f)
    iters = int(config["queries"]["pagerank_pull"]["iterations"])
    mix = {"sessions": args.queries, "mix": {"pagerank_pull": args.queries}}
    readings = {}
    for seed in args.seeds:
        src, dst = kronecker_edges(config, seed)
        g = reference.Graph(src, dst, 1 << int(config["SCALE"]))
        dampings = [q.param for q in QueryStream(mix, config, g.out_deg, vertex_map(config, seed), seed, stream=0).batch()]
        readings[seed] = control_errors(g, dampings, iters)
    least = min(min(v) for v in readings.values())
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "config": args.config,
        "control_pagerank_max_rel_err": readings,
        "least": least,
        "limit": config["limits"]["pagerank_max_rel_err"],
        "control_fails": least > config["limits"]["pagerank_max_rel_err"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
