"""Tensor-parallel serving equivalence (PR 7 tentpole gate).

Two families of tests, both on the 8 forced host CPU devices:

1. Engine token identity: ``ServeEngine(..., mesh=...)`` must generate
   byte-identical token streams to the single-device engine, for every
   orthogonal serving feature (fp32 / int8 / reuse-LUT / fused-QKV /
   multi-LoRA / paged KV) at mesh (1, 2) (head-sharded KV: n_kv_heads=2
   divides model=2) and mesh (1, 8) (sequence-sharded KV: 2 % 8 != 0, so
   the rules fall back to cache_seq="model" and decode routes through
   ``kernels.sharded_decode``). The fast subset runs in tier-1; the full
   matrix is ``slow``-marked and runs in CI's multi_device lane.

2. ``decode_attention_seqsharded`` goldens (int8-KV codes + scales)
   against BOTH dense ``decode_attention_ref`` and
   ``paged_decode_attention_ref`` on the scattered-equivalent pool, plus
   the length-0-row exact-zero convention the online-softmax kernels
   share (l == 0 -> acc / max(l, eps) == 0, not NaN).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ref import decode_attention_ref, paged_decode_attention_ref
from repro.kernels.sharded_decode import decode_attention_seqsharded
from repro.launch.mesh import make_host_mesh
from repro.models.model import get_model

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, vocab_pad_multiple=64, dtype="float32")

PROMPT_LENS = (5, 9, 3, 12, 7, 4)

# engine kwargs per serving feature; "lora" is synthesized in _generate
MODES = {
    "fp32": {},
    "int8": dict(quantize=True),
    "reuse": dict(quantize=True, impl="reuse"),
    "fused": dict(quantize=True, fuse_qkv=True),
    "lora": dict(quantize=True),
    "paged": dict(quantize=True, paged=True, kv_block_size=8),
    # the Pallas kernels themselves, run per shard (kernels/ops.py)
    "kernels": dict(quantize=True, impl="pallas_interpret"),
    "kernels_paged": dict(quantize=True, impl="pallas_interpret",
                          paged=True, kv_block_size=8),
}


@pytest.fixture(scope="module")
def base_params(eight_cpu_devices):
    api = get_model(CFG)
    return api.init(jax.random.PRNGKey(0))


def _generate(params, mesh, mode):
    from repro.launch.serve import make_synthetic_adapters
    from repro.serve.engine import ServeEngine

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in PROMPT_LENS]
    reg, names = None, [None] * len(prompts)
    if mode == "lora":
        reg, ns = make_synthetic_adapters(CFG, 2)
        names = [None if i % 3 == 0 else ns[i % 2]
                 for i in range(len(prompts))]
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, mesh=mesh,
                      adapters=reg, **MODES[mode])
    return eng.generate(prompts, max_new=8, adapters=names)


def _assert_token_identical(params, mode, model_size):
    base = _generate(params, None, mode)
    got = _generate(params, make_host_mesh(1, model_size), mode)
    assert got == base, (
        f"mesh (1, {model_size}) {mode} tokens diverge from single-device")


# fast subset (tier-1): one head-sharded mode pair at mesh 2
@pytest.mark.parametrize("mode", ["fp32", "int8"])
def test_engine_token_identity_mesh2(base_params, mode):
    _assert_token_identical(base_params, mode, 2)


# Mosaic kernels cannot be partitioned by GSPMD: under a mesh each runs
# once per shard in a shard_map, which must not change a token
@pytest.mark.parametrize("mode", ["kernels", "kernels_paged"])
def test_engine_kernels_token_identity_mesh2(base_params, mode):
    _assert_token_identical(base_params, mode, 2)


# full matrix: remaining features x {head-sharded, seq-sharded} meshes
@pytest.mark.slow
@pytest.mark.parametrize("mode", ["reuse", "fused", "lora", "paged"])
def test_engine_token_identity_mesh2_full(base_params, mode):
    _assert_token_identical(base_params, mode, 2)


@pytest.mark.slow
@pytest.mark.parametrize("mode", sorted(MODES))
def test_engine_token_identity_mesh8(base_params, mode):
    _assert_token_identical(base_params, mode, 8)


def test_mesh1_is_single_device_program(base_params):
    """A (1, 1) mesh resolves every spec to full replication, so the
    engine compiles the exact unsharded computation (size-1 axes are
    skipped by resolve_spec) — tokens trivially identical."""
    _assert_token_identical(base_params, "int8", 1)


# ---------------------------------------------------------------------------
# decode_attention_seqsharded goldens (satellite 4)
# ---------------------------------------------------------------------------

def _seqsharded_case(lengths, seed=0):
    """Random int8-KV decode state: caches hold codes, scales ride along.

    Returns (inputs dict, expected updated numpy caches/scales)."""
    b, s, h, hk, d = len(lengths), 32, 4, 2, 16
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    k = rng.integers(-127, 128, size=(b, s, hk, d)).astype(np.int8)
    v = rng.integers(-127, 128, size=(b, s, hk, d)).astype(np.int8)
    ks = rng.uniform(0.01, 0.05, size=(b, s, hk, 1)).astype(np.float32)
    vs = rng.uniform(0.01, 0.05, size=(b, s, hk, 1)).astype(np.float32)
    nk = rng.integers(-127, 128, size=(b, hk, d)).astype(np.int8)
    nv = rng.integers(-127, 128, size=(b, hk, d)).astype(np.int8)
    nks = rng.uniform(0.01, 0.05, size=(b, hk, 1)).astype(np.float32)
    nvs = rng.uniform(0.01, 0.05, size=(b, hk, 1)).astype(np.float32)
    length = np.asarray(lengths, np.int32)
    pos = length - 1                       # write slot; -1 when length == 0
    exp = {"k": k.copy(), "v": v.copy(), "ks": ks.copy(), "vs": vs.copy()}
    for i, p in enumerate(pos):
        if p >= 0:
            exp["k"][i, p], exp["v"][i, p] = nk[i], nv[i]
            exp["ks"][i, p], exp["vs"][i, p] = nks[i], nvs[i]
    inputs = dict(q=q, k=k, v=v, ks=ks, vs=vs, nk=nk, nv=nv, nks=nks,
                  nvs=nvs, pos=pos, length=length)
    return inputs, exp


def _run_seqsharded(inputs, model_size=4):
    mesh = make_host_mesh(1, model_size)
    i = {k: jnp.asarray(a) for k, a in inputs.items()}
    return decode_attention_seqsharded(
        i["q"], i["k"], i["v"], i["nk"], i["nv"], i["pos"], i["length"],
        mesh, seq_axes=("model",), batch_axes=(),
        k_scale=i["ks"], v_scale=i["vs"],
        new_k_scale=i["nks"], new_v_scale=i["nvs"])


def test_seqsharded_int8_matches_dense_and_paged_refs(eight_cpu_devices):
    """Golden: seq-sharded fused update+attend == dense ref on the
    manually scattered cache == paged ref on the block-pool layout."""
    inputs, exp = _seqsharded_case([5, 32, 17, 1])
    out, k2, v2, ks2, vs2 = _run_seqsharded(inputs)

    # the local masked scatter is exact (int8 codes + f32 scales)
    np.testing.assert_array_equal(np.asarray(k2), exp["k"])
    np.testing.assert_array_equal(np.asarray(v2), exp["v"])
    np.testing.assert_array_equal(np.asarray(ks2), exp["ks"])
    np.testing.assert_array_equal(np.asarray(vs2), exp["vs"])

    dense = decode_attention_ref(
        jnp.asarray(inputs["q"]), jnp.asarray(exp["k"]), jnp.asarray(exp["v"]),
        jnp.asarray(inputs["length"]),
        k_scale=jnp.asarray(exp["ks"]), v_scale=jnp.asarray(exp["vs"]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-4, atol=1e-6)

    # identity block tables: row b's sequence lives in blocks
    # 1 + b*nb .. 1 + b*nb + nb - 1 (block 0 is the trash block)
    b, s, hk, d = exp["k"].shape
    bs = 8
    nb = s // bs

    def pool(cache):
        trash = np.zeros((1, bs) + cache.shape[2:], cache.dtype)
        blocks = cache.reshape(b * nb, bs, *cache.shape[2:])
        return jnp.asarray(np.concatenate([trash, blocks]))

    tables = jnp.asarray(
        1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb))
    paged = paged_decode_attention_ref(
        jnp.asarray(inputs["q"]), pool(exp["k"]), pool(exp["v"]), tables,
        jnp.asarray(inputs["length"]),
        k_scale=pool(exp["ks"]), v_scale=pool(exp["vs"]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(paged),
                               rtol=1e-4, atol=1e-6)


def test_seqsharded_length0_row_is_exact_zero(eight_cpu_devices):
    """length == 0 rows produce EXACT zeros (l == 0 -> acc/max(l, eps)),
    never NaN, and write nothing into any shard's cache rows."""
    inputs, exp = _seqsharded_case([0, 3])
    out, k2, v2, ks2, vs2 = _run_seqsharded(inputs, model_size=2)
    out = np.asarray(out)
    assert np.all(out[0] == 0.0), "length-0 row must be exactly zero"
    assert not np.any(np.isnan(out))
    # row 0's pos is -1: no shard owns it, the cache is untouched
    np.testing.assert_array_equal(np.asarray(k2)[0], inputs["k"][0])
    np.testing.assert_array_equal(np.asarray(ks2)[0], inputs["ks"][0])
    # row 1 still behaves
    dense = decode_attention_ref(
        jnp.asarray(inputs["q"]), jnp.asarray(exp["k"]), jnp.asarray(exp["v"]),
        jnp.asarray(inputs["length"]),
        k_scale=jnp.asarray(exp["ks"]), v_scale=jnp.asarray(exp["vs"]))
    np.testing.assert_allclose(out[1], np.asarray(dense)[1],
                               rtol=1e-4, atol=1e-6)
