"""Sweep of offered load for an open-loop cell, to find the knee once.

    python3 bench/knee.py --workload <name> --seed <n> --seconds <s> \\
        --rates 50,100,200,400

One process and one set-up; for each rate a window of the cell's traffic
at that rate (other seeds of the same mix). Prints, per rate, the requests
completed per second, the median, 95th percentile and largest latency, and
how long after the window the last request finished: past the knee the
queue grows through the window and that lag grows with it. The rate a cell
runs at is then written into its traffic file as a number.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse

    import numpy as np

    from bench import arrivals, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("knee: JAX found no TPU; refusing to run")
        return 2
    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    cfg, mix = cell["config"], dict(cell["traffic"])
    sut = harness.system(mix).build(cfg, mix, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix["arrivals"] = {**mix["arrivals"], "rate_per_s": rate}
        reqs = arrivals.requests(mix, args.seconds, args.seed + 1 + i,
                                 n_items=getattr(sut, "n_items", None))
        sut.prepare(reqs)
        out = harness.open_loop(sut, reqs, harness.Spans(False))
        lat = out["latency_s"]
        print(json.dumps({
            "rate_per_s": rate, "attempted": out["attempted"],
            "failed": out["failed"],
            "completed_per_s": len(lat) / out["window_s"],
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "max_ms": 1e3 * float(lat.max()),
            "lag_s": out["window_s"] - args.seconds}), flush=True)
        time.sleep(0.5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
