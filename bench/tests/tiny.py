"""A reduced copy of a cell for CPU tests: same code paths, tiny widths."""

import copy
import json

from bench import cell

SIZES = dict(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=500, vocab_pad_multiple=64)


def tiny(workload: str):
    """(entry, configuration, mix) of ``<config>.<traffic>``, reduced; read
    from the configuration and traffic files by name, so a cell can be
    tested before ``BENCHMARK.json`` lists it."""
    config, mix_name = workload.split(".")
    conf = copy.deepcopy(cell.load_config(config))
    mix = json.loads((cell.BENCH / "traffic" / f"{mix_name}.json").read_text())
    entry = {"name": workload, "config": config, "traffic": mix_name,
             "chips": 1}
    conf.update(SIZES)
    if mix["kind"] == "open":
        conf["serve"].update(kv_block_size=16, prefill_budget=64,
                             max_len=512, num_blocks=200, n_slots=4)
        mix["rate_rps"] = 4.0
        mix["prompt"].update(median=100, max=300)
        mix["output"].update(median=16, min=4, max=64)
    else:
        conf["serve"].update(kv_block_size=16, prefill_budget=16,
                             max_len=1024, num_blocks=300, n_slots=3)
        mix.update(sessions=3, turns=4, system=64)
        mix["history"].update(median=128, min=64, max=256)
        mix["tool"].update(median=24, min=16, max=48)
        mix["reply"].update(median=12, min=4, max=32)
    return entry, conf, mix
