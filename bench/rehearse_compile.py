"""Compile a cell's serving programs for a described TPU v5e, without the
chip, and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python bench/rehearse_compile.py \
        [granite-3-8b-int8.chat glm4-9b-int8.sessions ...]

For each configuration it compiles, at full width and depth, the decode
chunk (``decode_chunk`` steps over every slot) and the largest prefill
programs of its lattice: a first chunk and a continuation chunk against
the longest cached prefix, at the widest wave the traffic can make. The
programs are the engine's own jitted closures, lowered with shapes only.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import cell, traffic, weights  # noqa: E402

GB = 1e9


def fake_engine(conf: dict):
    """An engine object carrying only what its program builders read."""
    from repro.models.model import get_model
    from repro.serve.engine import EngineStats, ServeEngine
    cfg = cell.model_config(conf)
    s = conf["serve"]
    eng = object.__new__(ServeEngine)
    eng.__dict__.update(
        cfg=cfg, api=get_model(cfg), kv_block_size=s["kv_block_size"],
        _pool_leaves=["k", "v"], registry=None, mesh=None,
        max_len=s["max_len"], max_blocks=-(-s["max_len"] // s["kv_block_size"]),
        eos_id=None, greedy=True, _prefill_cache={}, _chunk_fns={},
        stats=EngineStats(), n_slots=s["n_slots"])
    return eng


def structs(conf: dict, sharding, num_blocks: int):
    """Shape-only params and paged cache on ``sharding``."""
    m, s = conf, conf["serve"]
    w = jax.eval_shape(lambda: weights.draw_all(weights.base_key(0), m))
    params = cell.pack_params(w, conf)
    put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
    params = jax.tree_util.tree_map(put, params)
    L, hk, hd = m["num_hidden_layers"], m["num_key_value_heads"], m["head_dim"]
    bs, B = s["kv_block_size"], s["n_slots"]
    mb = -(-s["max_len"] // bs)
    kv = jax.ShapeDtypeStruct((L, num_blocks, bs, hk, hd), jnp.bfloat16,
                              sharding=sharding)
    cache = {"k": kv, "v": kv,
             "pos": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sharding),
             "block_tables": jax.ShapeDtypeStruct((B, mb), jnp.int32,
                                                  sharding=sharding)}
    return params, cache


def i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def report(tag, compiled, t):
    ma = compiled.memory_analysis()
    print(f"  {tag}: compile {t:.1f} s, args {ma.argument_size_in_bytes / GB:.3f}"
          f" GB, out {ma.output_size_in_bytes / GB:.3f} GB, alias "
          f"{ma.alias_size_in_bytes / GB:.3f} GB, temp "
          f"{ma.temp_size_in_bytes / GB:.3f} GB", flush=True)


def rehearse(name: str, num_blocks: int, programs=None):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import ops
    ops._on_tpu = lambda: True      # the described chip's kernel paths
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    conf = cell.load_config(name)
    s = conf["serve"]
    eng = fake_engine(conf)
    params, cache = structs(conf, one, num_blocks)
    B, bs = s["n_slots"], s["kv_block_size"]
    print(f"{name}: {num_blocks} blocks of {bs}", flush=True)
    for tag, (wb, pl, npb) in (programs or {}).items():
        fn = eng._get_paged_prefill(wb, pl, npb)
        t0 = time.time()
        c = fn.lower(cache, params, i32((wb, pl), one), i32((wb,), one),
                     i32((wb,), one), i32((wb, max(npb, 1)), one),
                     i32((wb, pl // bs), one)).compile()
        report(f"prefill {tag} wb={wb} pl={pl} npb={npb}", c,
               time.time() - t0)
    n = s["decode_chunk"]
    fn = eng._get_chunk_fn(n)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    t0 = time.time()
    c = fn.lower(params, i32((B,), one), cache, key,
                 jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one),
                 i32((B,), one), i32((B,), one)).compile()
    report(f"decode chunk n={n} B={B}", c, time.time() - t0)


def main(argv):
    """Compile the widest first chunk and the longest-prefix chunk of each
    configuration's lattice (``harness.lattice``), then its decode chunk."""
    import json
    from bench import harness
    for cell_name in argv or [w["name"] for w in cell.load_spec()["workloads"]]:
        name, mix_name = cell_name.split(".")
        conf = cell.load_config(name)
        mix = json.loads((cell.BENCH / "traffic" / f"{mix_name}.json")
                         .read_text())
        reqs = (traffic.open_loop(mix, 0, 51, conf["vocab_size"])
                if mix["kind"] == "open" else None)
        shapes = harness.lattice(conf, harness.first_wave(conf, reqs),
                                 harness.prefix_blocks(conf))
        first = max((s for s in shapes if s[2] == 0),
                    key=lambda s: (s[0] * s[1], s))
        longest = max(shapes, key=lambda s: (s[2], s[0] * s[1]))
        rehearse(name, conf["serve"]["num_blocks"],
                 {"first": first, "prefix": longest})


if __name__ == "__main__":
    main(sys.argv[1:])
