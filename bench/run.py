"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process: check the chip, draw the weights from the seed on the
device, build the program's ``ServeEngine``, warm every program shape
the cell's traffic can reach, serve the traffic for ``--seconds``, then
check the served tokens against the float32 reference. The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and last the compared numbers beside their limits under ``checks``).
Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or on a chip missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import cell, harness, reference, traffic  # noqa: E402

TRACE_DIR = ROOT / "bench" / ".trace"


class Compiles:
    """Backend compiles seen through JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        self.seconds = 0.0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs
        mon.register_event_duration_secs_listener(on_duration)


def load_reader(metric: str):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sample_open(eng, client, t0, seconds, seed, want_tokens):
    """Finished requests whose tokens reached the client in the window: the
    one with the most served tokens, then others drawn from the seed until
    ``want_tokens`` served tokens are in the sample."""
    end = t0 + seconds
    done = [r for r in eng.finished
            if r.finish_reason in ("max_new", "eos", "cache_full")
            and client.times.get(r.rid) and client.times[r.rid][-1] <= end]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    rng = traffic.rng_for(seed, 3)
    first = max(done, key=lambda r: len(r.tokens))
    order = [first] + [done[i] for i in rng.permutation(len(done))
                       if done[i] is not first]
    out, n = [], 0
    for r in order:
        out.append(r)
        n += len(r.tokens)
        if n >= want_tokens:
            break
    return [(np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)]),
             len(r.prompt)) for r in out]


def sample_sessions(eng, client, t0, seconds, turn_of, sess, seed,
                    want_tokens):
    """Sessions with finished turns: each sampled as its context through
    its last finished turn, every reply token in it judged."""
    end = t0 + seconds
    last = {}
    for r in eng.finished:
        if r.finish_reason not in ("max_new", "eos", "cache_full"):
            continue
        ts = client.times.get(r.rid)
        if not ts or ts[-1] > end:
            continue
        k, t = turn_of[r.rid]
        if k not in last or t > last[k][1]:
            last[k] = (r, t)
    if not last:
        return []
    rng = traffic.rng_for(seed, 3)
    keys = sorted(last)
    first = max(keys, key=lambda k: len(last[k][0].prompt))
    order = [first] + [keys[i] for i in rng.permutation(len(keys))
                       if keys[i] != first]
    out, n = [], 0
    for k in order:
        r, t = last[k]
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        # served positions: every reply of this session, turn by turn
        served = np.zeros(len(seq), bool)
        ctx = len(sess[k].context)
        for j in range(t + 1):
            ctx += len(sess[k].tools[j])
            rlen = sess[k].replies[j]
            served[ctx:ctx + rlen] = True
            ctx += rlen
        out.append((seq, served))
        n += int(served.sum())
        if n >= want_tokens:
            break
    return out


def positions(sample):
    """(sequences, positions whose next token was served)."""
    seqs, at = [], []
    for seq, mark in sample:
        if isinstance(mark, (int, np.integer)):
            pos = np.arange(mark - 1, len(seq) - 1)
        else:
            pos = np.nonzero(mark)[0] - 1
        seqs.append(seq)
        at.append(pos)
    return seqs, at


def widest(gaps):
    """Widest gap over every judged token (+inf when none was judged)."""
    return float(np.concatenate(gaps).max()) if gaps else float("inf")


def run(workload, seed, seconds, trace, *, conf=None, mix=None, entry=None,
        check_chip=True, log=sys.stderr, judge=True, control=False):
    """One run; returns the result dict (the printed line)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    spec = cell.load_spec()
    if conf is None:
        entry, conf, mix = cell.resolve(workload)
    chips = entry["chips"]
    if check_chip:
        devs, peaks = harness.check_device(chips)
    else:
        devs = jax.devices()[:chips]
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text()
                           )["devices"]["TPU v5 lite"]
    enable_compile_cache()
    compiles = Compiles()
    vocab = conf["vocab_size"]

    # -- set-up: weights, engine, traffic, warm-up ---------------------------
    eng = harness.build_engine(conf, seed)
    if mix["kind"] == "open":
        reqs = traffic.open_loop(mix, seed, seconds, vocab)
        sess = None
        harness.capacity_check(conf, mix, reqs=reqs)
        wave = harness.first_wave(conf, reqs)
    else:
        reqs = None
        sess = traffic.sessions(mix, seed, vocab)
        harness.capacity_check(conf, mix, sess=sess)
        wave = 1
    harness.warm(eng, conf, harness.lattice(conf, wave,
                                            harness.prefix_blocks(conf)),
                 seed)
    if sess is not None:
        harness.prefill_sessions(eng, sess)
    jax.block_until_ready(eng.cache)
    eng.stats.__init__()
    client = harness.Client(time.perf_counter)
    work = harness.WorkLog(eng)
    n_compiles = compiles.n
    setup_s = time.perf_counter() - T_START

    # -- the window -------------------------------------------------------------
    span = jax.profiler.TraceAnnotation if trace else (
        lambda name: nullcontext())
    if trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    if reqs is not None:
        t0 = harness.serve_open(eng, reqs, seconds, client, work, span)
        turn_of = None
    else:
        t0, turn_of = harness.serve_sessions(eng, sess, seconds, client,
                                             work, span)
    t_end = time.perf_counter()
    jax.block_until_ready(eng.cache)
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.n - n_compiles
    queue_end = len(eng.queue) + sum(s is not None for s in eng.slots)
    got = harness.e2e(client, t0, seconds)
    stats = eng.stats
    mem = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    # -- sample what the window served, then free the program ------------------
    want = 300
    if reqs is not None:
        sample = sample_open(eng, client, t0, seconds, seed, want)
    else:
        sample = sample_sessions(eng, client, t0, seconds, turn_of, sess,
                                 seed, want)
    attempted = sum(1 for d in client.due.values() if d <= t0 + seconds)
    failed = sum(1 for r in eng.finished
                 if r.finish_reason in ("rejected", "expired", "cancelled"))
    engine_stats = stats.as_dict()
    work.eng = None
    del eng, stats
    gc.collect()

    # -- metrics ----------------------------------------------------------------
    e2e_all = {
        "ttft_p90_ms": harness.pct(got["ttft_s"], 90) * 1e3,
        "ttft_p50_ms": harness.pct(got["ttft_s"], 50) * 1e3,
        "itl_p95_ms": harness.pct(got["gaps_s"], 95) * 1e3,
        "output_tok_s": got["tokens"] / seconds,
        "setup_s": setup_s,
    }
    name = entry["name"]
    metrics, breakdown = {}, None
    if not trace:
        for mt in spec["end_to_end"]:
            if name in mt.get("workloads", [name]):
                metrics[mt["name"]] = {"value": e2e_all[mt["name"]],
                                       "unit": mt["unit"]}
    else:
        from bench import trace as tr
        red = tr.reduce(TRACE_DIR, t0, t_end)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx = {"work": work, "trace": red, "peaks": peaks, "conf": conf,
               "stats": engine_stats, "seconds": seconds}
        for mt in spec["per_layer"]:
            if name not in mt.get("workloads", [name]):
                continue
            v = load_reader(mt["name"])(ctx)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}

    # -- correctness --------------------------------------------------------------
    limits = json.loads((ROOT / "bench" / "limits.json").read_text())
    limit = limits[conf["name"]]["logit_gap"]
    seqs, at = positions(sample)
    t_ref = time.perf_counter()
    if not judge:
        seqs, at = [], []
    served, ctrl = (reference.judge(seed, conf, seqs, at, control=control)
                    if seqs else ([], []))
    ref_s = time.perf_counter() - t_ref
    program_gap = widest(served)
    if control:
        # the control in the program's place: its tokens are judged by
        # the same comparison and limit as the program's
        served = ctrl
    all_gaps = np.concatenate(served) if served else np.array([])
    gap = widest(served)
    n_judged = int(len(all_gaps))
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "judged_tokens": {"value": n_judged, "limit": 1},
        "failed_requests": {"value": failed, "limit": 0},
        "window_compiles": {"value": window_compiles, "limit": 0},
    }
    correct = (gap <= limit and n_judged >= 1 and failed == 0
               and window_compiles == 0 and attempted > 0)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {
        "requests_done": sum(1 for ts in client.times.values() if ts),
        "tokens": got["tokens"], "setup_compiles": n_compiles,
        "compile_s": compiles.seconds, "reference_s": ref_s,
        "window_s": t_end - t0, "sampled_sequences": len(seqs),
        "in_engine_at_end": queue_end,
        "program_gap": program_gap,
        "ttft_halves_ms": harness.halves(client, t0, seconds),
        "prefix_hit_tokens": engine_stats["prefix_hit_tokens"],
        "prefill_tokens": engine_stats["prefill_tokens"],
        "work_prefill_tokens": sum(e - s for s, e in work.prefill_pos),
        "work_tokens": len(work.decode_ctx) + work.prefill_rows,
        "engine_tokens": engine_stats["decode_tokens"] + work.prefill_rows,
        "decode_steps": engine_stats["steps"], "e2e": e2e_all}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=log)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    a = ap.parse_args(argv)
    try:
        res = run(a.workload, a.seed, a.seconds, bool(a.trace))
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
