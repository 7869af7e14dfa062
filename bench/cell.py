"""A benchmark cell: its configuration file, its traffic file, and the
serving engine built from them.

Everything is found by name from ``BENCHMARK.json``: a workload names a
configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(workload: str, root: Path = ROOT):
    """(workload entry, configuration dict, traffic dict) for a name."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(root / cfg_entry["file"]) as f:
        conf = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return w, conf, traffic


def load_config(name: str) -> dict:
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def model_config(conf: dict, **overrides):
    """The program's ModelConfig for a configuration file."""
    from repro.configs import get_config
    base = get_config(conf["registry"])
    fields = dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"],
        vocab_pad_multiple=conf["vocab_pad_multiple"],
        norm_eps=conf["rms_norm_eps"], rope_theta=conf["rope_theta"],
        qkv_bias=conf["attention_bias"],
        tie_embeddings=conf["tie_word_embeddings"],
        quant_bits=conf["quant"]["bits"],
        decode_chunk=conf["serve"]["decode_chunk"],
        dtype="bfloat16", act="swiglu", norm="rmsnorm")
    fields.update(overrides)
    return dataclasses.replace(base, **fields)


def pack_params(w: dict, conf: dict):
    """The program's parameter tree from :func:`weights.draw_all` arrays
    (also works on ``ShapeDtypeStruct`` trees)."""
    from repro.core.quantization import QTensor
    bits = conf["quant"]["bits"]

    def qt(pair):
        codes, absmax = pair
        return QTensor(codes=codes, scale=absmax, codebook=None, bits=bits,
                       mode="affine", granularity="per_channel",
                       group_size=128, packed=False, shape=tuple(codes.shape))

    lw = w["layers"]
    attn = {k: qt(lw[k]) for k in ("wq", "wk", "wv", "wo")}
    for k in ("wq_bias", "wk_bias", "wv_bias"):
        if k in lw:
            attn[k] = lw[k]
    return {
        "embed": {"embedding": w["embedding"], "lm_head": qt(w["head"])},
        "layers": {"ln1": {"scale": lw["ln1"]}, "attn": attn,
                   "ln2": {"scale": lw["ln2"]},
                   "ffn": {k: qt(lw[k]) for k in ("gate", "up", "down")}},
        "final_norm": {"scale": w["final_norm"]},
    }


def engine_kwargs(conf: dict) -> dict:
    s = conf["serve"]
    return dict(n_slots=s["n_slots"], max_len=s["max_len"], quantize=False,
                paged=True, kv_block_size=s["kv_block_size"],
                num_blocks=s["num_blocks"] or None,
                prefill_budget=s["prefill_budget"],
                decode_chunk=s["decode_chunk"], greedy=s["greedy"],
                long_prompt="reject")
