"""Record the small trace that test_trace.py reads, on a TPU.

    PYTHONPATH=src:. python3 bench/tests/record_trace.py

Serves the reduced chat cell (bench/tests/tiny.py) through the harness
for a short traced window and keeps the profiler's .xplane.pb as
bench/testdata/tiny_chat.xplane.pb, with the harness's own numbers for
it in bench/testdata/tiny_chat.json.
"""

import glob
import json
import os
import shutil
import sys

sys.path.insert(0, "src")
sys.path.insert(0, ".")

from bench import run  # noqa: E402
from bench.tests.tiny import tiny  # noqa: E402


def main():
    entry, conf, mix = tiny("granite-3-8b-int8.chat")
    # lane-sized heads, so every kernel lowers for the chip as it does at
    # full width
    conf.update(hidden_size=256, num_attention_heads=2,
                num_key_value_heads=1, head_dim=128, intermediate_size=512)
    res = run.run(entry["name"], 77, 1.5, True, conf=conf, mix=mix,
                  entry=entry)
    pb = sorted(glob.glob(str(run.TRACE_DIR / "**" / "*.xplane.pb"),
                          recursive=True))[-1]
    out = run.ROOT / "bench" / "testdata"
    os.makedirs(out, exist_ok=True)
    shutil.copy(pb, out / "tiny_chat.xplane.pb")
    keep = {"device": res["device"], "breakdown": res["breakdown"],
            "metrics": res["metrics"]}
    (out / "tiny_chat.json").write_text(json.dumps(keep, indent=1))
    print(json.dumps(keep))


if __name__ == "__main__":
    main()
