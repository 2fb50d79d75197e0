"""The control of a cell's comparison: the plain reference, computed one
precision step below what the configuration states, put in the program's
place. Its readings set the upper end of each limit.

    python3 bench/control.py --workload <name> --seeds 11,12,13 \\
        --seconds <s> [--precision high]

One process, one set-up per seed, a short window at the cell's own load
(so the comparison sees as many answers as a run does), then the
comparison twice: the control's readings and the program's. Prints one
JSON line per seed with both.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", default="high",
                    help="a precision below the configuration's, or a "
                         "fault the reference carries in the program's "
                         "place (sketch step: unchanged, half-batch, "
                         "altered)")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("control: JAX found no TPU; refusing to run")
        return 2
    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    for seed in map(int, args.seeds.split(",")):
        out = harness.measure(cell, seed, args.seconds, False,
                              time.perf_counter(), jax.devices()[:1],
                              control=args.precision)
        print(json.dumps({"control": args.precision, "seed": seed,
                          "checks": out["checks"],
                          "program": out["program_checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
