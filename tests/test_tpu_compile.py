"""Mosaic compiles of the serving path's Pallas kernels at repro-100m widths.

Each case lowers a kernel for one chip of a *described* TPU v5e (no chip
attached: the TPU compiler is installed and compiles for the description)
and compiles it, so a block shape or VMEM budget the chip's compiler would
refuse fails here, at no chip time. Nothing runs; results are checked by
the interpret-mode tests and by chip_smoke.py on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.repro_100m import CONFIG
from repro.kernels import ops

D, DFF = CONFIG.d_model, CONFIG.d_ff                      # 768, 2048
H, HK, HD = CONFIG.n_heads, CONFIG.n_kv_heads, CONFIG.head_dim   # 12, 4, 64
B, S, BLOCK = 4, 256, 16          # decode slots, max_len, KV block tokens
NB, MB = 2 * B * (S // BLOCK) + 2, S // BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _decode(k_scale=False, paged=False):
    from repro.kernels import decode_attention as da
    from repro.kernels import paged_decode_attention as pda

    if paged:
        return lambda q, k, v, bt, ln, *sc: pda.paged_decode_attention_pallas(
            q, k, v, bt, ln, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None)
    return lambda q, k, v, ln, *sc: da.decode_attention_pallas(
        q, k, v, ln, k_scale=sc[0] if sc else None,
        v_scale=sc[1] if sc else None)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_attention_compiles(one_chip, kv):
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    shapes = [((B, H, HD), jnp.bfloat16), ((B, S, HK, HD), dt),
              ((B, S, HK, HD), dt), ((B,), jnp.int32)]
    if kv == "int8":
        shapes += [((B, S, HK, 1), jnp.float32)] * 2
    _compile(one_chip, _decode(), *shapes)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv):
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    shapes = [((B, H, HD), jnp.bfloat16), ((NB, BLOCK, HK, HD), dt),
              ((NB, BLOCK, HK, HD), dt), ((B, MB), jnp.int32),
              ((B,), jnp.int32)]
    if kv == "int8":
        shapes += [((NB, BLOCK, HK, 1), jnp.float32)] * 2
    _compile(one_chip, _decode(paged=True), *shapes)


# (m, K, N): decode m = slots, prefill m = 4 x 32; one tensor-parallel
# shard of a column-parallel projection (N / 4 = 192 lanes); a 512-token
# prefill chunk in one row block
MATMULS = [(B, D, D), (B, D, HK * HD), (B, D, DFF), (B, DFF, D),
           (128, DFF, D), (B, D, D // 4), (512, DFF, D),
           # GLM-4-9B's FFN in a 128-token prefill chunk: ragged 256- and
           # 512-lane blocks on d_ff 13696 = 107·128
           (128, 4096, 13696), (128, 13696, 4096)]


def _blocks(m, k, n):
    return ops.pick_blocks(m, k, n)[:3]


@pytest.mark.parametrize("m,k,n", MATMULS)
def test_axllm_matmul_int8_compiles(one_chip, m, k, n):
    from repro.kernels.axllm_matmul import axllm_matmul_pallas
    mp = m + ops.pick_blocks(m, k, n)[3]
    _compile(one_chip, lambda x, c, s: axllm_matmul_pallas(
        x, c, s, blocks=_blocks(m, k, n)),
        ((mp, k), jnp.bfloat16), ((k, n), jnp.int8), ((1, n), jnp.float32))


@pytest.mark.parametrize("m,k,n", MATMULS[:1] + MATMULS[3:5])
def test_axllm_matmul_nf4_packed_compiles(one_chip, m, k, n):
    from repro.kernels.axllm_matmul import axllm_matmul_pallas
    mp = m + ops.pick_blocks(m, k, n)[3]
    _compile(one_chip, lambda x, c, s, cb: axllm_matmul_pallas(
        x, c, s, cb, bits=4, packed=True, blocks=_blocks(m, k, n)),
        ((mp, k), jnp.bfloat16), ((k, n // 2), jnp.uint8),
        ((1, n), jnp.float32), ((16,), jnp.float32))


@pytest.mark.parametrize("count", [False, True], ids=["serve", "counting"])
@pytest.mark.parametrize("m,k,n", [(B, D, D), (128, D, D), (512, D, D)])
def test_reuse_matmul_compiles(one_chip, m, k, n, count):
    from repro.kernels.reuse_matmul import reuse_matmul_pallas
    mp = m + ops.pick_blocks(m, k, n)[3]
    _compile(one_chip, lambda x, c, s, lv: reuse_matmul_pallas(
        x, c, s, lv, blocks=_blocks(m, k, n), count=count),
        ((mp, k), jnp.bfloat16), ((k, n), jnp.int8), ((1, n), jnp.float32),
        ((128,), jnp.float32))


@pytest.mark.parametrize("s", [32, S])
def test_flash_attention_compiles(one_chip, s):
    from repro.kernels.flash_attention import flash_attention_pallas
    _compile(one_chip, flash_attention_pallas,
             ((4, s, H, HD), jnp.bfloat16), ((4, s, HK, HD), jnp.bfloat16),
             ((4, s, HK, HD), jnp.bfloat16))


def _defs(text):
    """HLO instruction name -> (result type, opcode, operand text)."""
    return {m[1]: (m[2], m[3], m[4]) for m in re.finditer(
        r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(([^)]*)\)", text,
        re.M)}


def _weight_kernel_code_copies(one_chip, program, cfg=CONFIG):
    """Compile ``program(params, cache)`` of the quantized model ``cfg``
    (repro-100m by default) for one described v5e chip; return the number
    of axllm_matmul_pallas calls and the s8 operands among them that are a
    copy of the codes.

    A parameter or tuple element is the codes as the program holds them,
    and a bitcast of one (the [1, K, N] view of a standalone weight) is no
    copy either; anything else must still be the whole [n_layers, K, N]
    stack (the compiler may prefetch a small one into fast memory)."""
    from repro.core.axllm_linear import deploy_quantize
    from repro.core.quantization import QuantConfig
    from repro.models import transformer as T

    params = jax.eval_shape(lambda: deploy_quantize(
        T.init_params(jax.random.PRNGKey(0), cfg), QuantConfig(bits=8)))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    put = lambda t: jax.tree_util.tree_map(             # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    text = jax.jit(program).lower(put(params), put(cache)).compile().as_text()
    defs = _defs(text)
    calls = re.findall(r"^\s*(?:ROOT )?%axllm_matmul_pallas[.\d]* = \S+ "
                       r"custom-call\(([^)]*)\)", text, re.M)
    stack = f"s8[{cfg.n_layers},"
    copies = []
    for operands in calls:
        for name in re.findall(r"%([\w.\-]+)", operands):
            ty, op, args = defs[name]
            while op == "bitcast":
                ty, op, args = defs[re.search(r"%([\w.\-]+)", args)[1]]
            if ty.startswith("s8[") and not ty.startswith(stack) \
                    and op not in ("parameter", "get-tuple-element"):
                copies.append((name, ty, op))
    return len(calls), copies


def test_decode_step_reads_codes_in_place(one_chip):
    """The quantized decode step hands every weight kernel the whole
    [n_layers, K, N] stack of int8 codes: no per-layer copy of the codes
    (an s8 [K, N] fusion or slice) feeds an axllm_matmul_pallas call."""
    from repro.models import transformer as T

    token = jnp.zeros((B,), jnp.int32)
    n, copies = _weight_kernel_code_copies(one_chip, lambda p, c: (
        T.decode_step(p, token, CONFIG, c, impl="pallas")))
    assert n >= 7                            # q, k, v, o, gate, up, down
    assert not copies, copies


def test_prefill_reads_codes_in_place(one_chip):
    """The same for a 512-token prefill (4 rows x 128), whose weight
    kernels take the whole chunk in one row block."""
    from repro.models import transformer as T

    tokens = jnp.zeros((B, S // 2), jnp.int32)
    n, copies = _weight_kernel_code_copies(one_chip, lambda p, c: (
        T.prefill(p, tokens, CONFIG, c, impl="pallas")))
    assert n >= 7
    assert not copies, copies


def _decode_step_blocks(one_chip, cfg, monkeypatch):
    """Compile ``cfg``'s quantized decode step over B slots for one
    described chip; return the code copies feeding a weight kernel (as
    :func:`_weight_kernel_code_copies`) and each weight-kernel call's
    (M, K, N, codes rank, blocks) as traced."""
    from repro.kernels import axllm_matmul as amm
    from repro.models import transformer as T

    calls, kernel = [], amm.axllm_matmul_pallas

    def spy(x, codes, scale, *args, blocks, **kw):
        calls.append((*x.shape, scale.shape[-1], codes.ndim, blocks))
        return kernel(x, codes, scale, *args, blocks=blocks, **kw)

    monkeypatch.setattr(amm, "axllm_matmul_pallas", spy)
    token = jnp.zeros((B,), jnp.int32)
    n, copies = _weight_kernel_code_copies(one_chip, lambda p, c: (
        T.decode_step(p, token, cfg, c, impl="pallas")), cfg)
    assert n >= 8 and calls
    return copies, calls


def test_glm4_decode_step_takes_full_lane_tiles(one_chip, monkeypatch):
    """GLM-4-9B at its published widths (two of its 40 layers: the layer
    scan compiles one body whatever the depth): the decode step compiles
    for a v5e, and its FFN kernels, whose d_ff 13696 = 107·128 has no
    factor of 256, take 512-lane tiles with a ragged last block on the
    whole [L, K, N] stack of codes, where they fell to 128 lanes."""
    import dataclasses
    from repro.configs.glm4_9b import CONFIG as GLM

    cfg = dataclasses.replace(GLM, n_layers=2)
    copies, calls = _decode_step_blocks(one_chip, cfg, monkeypatch)
    assert not copies, copies
    ffn = [c for c in calls if cfg.d_ff in c[1:3]]
    assert len(ffn) == 3                        # gate, up, down
    for m, k, n, rank, (bm, bk, bn) in ffn:
        assert rank == 3 and bk == 512 and bn == 512, (m, k, n, bk, bn)


def test_granite_decode_step_keeps_its_blocks(one_chip, monkeypatch):
    """Granite-3.0-8B's widths divide by 512: its decode step's weight
    kernels take exactly the blocks of the table without ragged tiles,
    the blocks its chip numbers were measured with."""
    import dataclasses
    from repro.configs.granite_3_8b import CONFIG as GRANITE

    cfg = dataclasses.replace(GRANITE, n_layers=2)
    copies, calls = _decode_step_blocks(one_chip, cfg, monkeypatch)
    assert not copies, copies
    assert len(calls) == 8                      # 7 per layer + the head
    for m, k, n, _, blocks in calls:
        assert blocks == ops.pick_blocks(m, k, n, ragged=False)[:3]
        assert blocks[1] == 512 and k % 512 == 0


_ITEM_BYTES = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2}


def _nbytes(ty):
    """Bytes of an HLO array type such as bf16[2,148,128,8,128]{...}; 0
    for a tuple or token."""
    m = re.match(r"(\w+)\[([\d,]*)\]", ty)
    if not m:
        return 0
    dims = [int(d) for d in m[2].split(",") if d]
    return math.prod(dims) * _ITEM_BYTES.get(m[1], 4)


def _fused_root(text, fusion):
    """Opcode of the root of the computation that fusion ``fusion``
    calls."""
    called = re.search(rf"%{re.escape(fusion)} = .*? calls=%([\w.\-]+)",
                       text)[1]
    body = re.search(rf"^%{re.escape(called)} .*?^}}", text, re.M | re.S)[0]
    return re.search(r"ROOT %[\w.\-]+ = \S+ ([\w\-]+)\(", body)[1]


# (config, slots, pool blocks of 128 tokens, table width): the cells' own
_POOLS = {"granite-3-8b": (4, 148, 32), "glm4-9b": (3, 450, 149)}


@pytest.mark.parametrize("arch", sorted(_POOLS))
def test_paged_decode_chunk_keeps_pool_in_place(one_chip, arch):
    """The engine's paged decode chunk (``decode_steps`` over
    ``T.decode_step``), at published widths and two layers, updates and
    reads the stacked KV pool in place: no copy, slice, write-back,
    relayout or fusion makes a value the size of one layer's pool, but
    the one-token scatter that updates the stack in place; every KV
    operand of the paged kernel is the whole stack; and the chunk needs
    less scratch memory than one layer's pool."""
    import dataclasses
    from repro.configs import get_config
    from repro.core.axllm_linear import deploy_quantize
    from repro.core.quantization import QuantConfig
    from repro.models import transformer as T
    from repro.serve.decode import decode_steps

    cfg = dataclasses.replace(get_config(arch), n_layers=2)
    b, nb, mb = _POOLS[arch]
    params = jax.eval_shape(lambda: deploy_quantize(
        T.init_params(jax.random.PRNGKey(0), cfg), QuantConfig(bits=8)))
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        cfg, b, nb, 128, mb, jnp.bfloat16))
    stack = cache["k"]
    layer_bytes = math.prod(stack.shape[1:]) * stack.dtype.itemsize

    def chunk(params, last, cache, rng, stop, gen, max_new):
        return decode_steps(
            lambda p, t, c: T.decode_step(p, t, cfg, c, impl="pallas"),
            params, last, cache, rng, stop, gen, max_new, n=8,
            vocab_size=cfg.vocab_size, max_len=mb * 128)

    put = lambda t: jax.tree_util.tree_map(             # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    rows = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        put(params), rows, put(cache), put(jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((b,), bool, sharding=one_chip), rows,
        rows).compile()
    text = compiled.as_text()
    defs = _defs(text)
    moved = [(name, ty, op) for name, (ty, op, _) in defs.items()
             if _nbytes(ty) >= layer_bytes
             and op.startswith(("copy", "dynamic-slice",
                                "dynamic-update-slice", "reshape", "fusion",
                                "slice"))
             and not (op == "fusion" and _fused_root(text, name) == "scatter")]
    assert not moved, moved

    whole = f"bf16[{','.join(map(str, stack.shape))}]"
    calls = re.findall(r"^\s*(?:ROOT )?%paged_decode_attention_pallas[.\d]* = "
                       r"\S+ custom-call\(([^)]*)\)", text, re.M)
    assert calls
    for operands in calls:
        kv = []
        for name in re.findall(r"%([\w.\-]+)", operands):
            ty, op, args = defs[name]
            while op == "bitcast":
                ty, op, args = defs[re.search(r"%([\w.\-]+)", args)[1]]
            if _nbytes(ty) >= layer_bytes:
                kv.append((name, ty, op))
        assert len(kv) == 2, kv                      # keys and values
        for name, ty, op in kv:
            assert ty.startswith(whole) or op in (
                "parameter", "get-tuple-element"), (name, ty, op)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
