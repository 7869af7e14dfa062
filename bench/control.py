"""Read the correctness check's two sides on the chip, for setting a
cell's limit: the program's widest served-token gap (sound runs) and the
control's. The control is the float32 reference with int4 weight codes,
one precision below the configuration's int8, put in the program's
place: its first-ranked tokens at the positions the program served are
judged by ``run.py``'s own comparison and limit, so ``correct`` has to
come out false.

    python3 bench/control.py --workload granite-3-8b-int8.chat \
        --seconds 51 --seeds 11,12,13

Each seed is a run of the cell as the benchmark makes it, at the cell's
own size and load; the seeds run one after another in one process.
"""

import argparse
import json
import sys

from run import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    for seed in [int(x) for x in a.seeds.split(",")]:
        res = run(a.workload, seed, a.seconds, False, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_gap": res["checks"]["logit_gap"]["value"],
                          "limit": res["checks"]["logit_gap"]["limit"],
                          "program_gap": res["info"]["program_gap"],
                          "judged": res["checks"]["judged_tokens"]["value"],
                          "reference_s": res["info"]["reference_s"],
                          "metrics": res["metrics"],
                          "setup_s": res["info"]["e2e"]["setup_s"]}),
              flush=True)


if __name__ == "__main__":
    sys.exit(main())
