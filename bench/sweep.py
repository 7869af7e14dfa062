"""Find the knee of an open-loop cell: serve it at each of a list of
arrival rates, in one process, and report for each whether the backlog
grew over the window.

    python3 bench/sweep.py --workload granite-3-8b-int8.chat --seed 7 \
        --seconds 40 --rates 0.5,0.7,0.9,1.1

The knee is the highest rate whose requests are all answered within the
window's pace: what is still queued or running at the end stays within
the slots, and the second half's median time to first token stays
within twice the first half's. The cell's traffic file then carries 0.8
of it as a number.
"""

import argparse
import copy
import json
import sys

from run import cell, run  # noqa: F401  (sets up the import path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args()
    entry, conf, mix = cell.resolve(a.workload)
    for rate in [float(r) for r in a.rates.split(",")]:
        m = copy.deepcopy(mix)
        m["rate_rps"] = rate
        res = run(a.workload, a.seed, a.seconds, False, conf=conf, mix=m,
                  entry=entry, judge=False)
        i = res["info"]
        first, second = i["ttft_halves_ms"]
        held = (i["in_engine_at_end"] <= conf["serve"]["n_slots"]
                and second <= 2 * first)
        print(json.dumps({"rate_rps": rate, "sustained": held,
                          "attempted": res["attempted"],
                          "done": i["requests_done"],
                          "in_engine_at_end": i["in_engine_at_end"],
                          "ttft_halves_ms": i["ttft_halves_ms"],
                          "e2e": i["e2e"]}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
