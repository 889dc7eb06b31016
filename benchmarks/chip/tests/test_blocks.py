"""Block modules against what the harness did before it had them: the
dense configurations get the same ModelConfig, the same parameter tree bit
for bit and the same counts of work, kept here as the yardstick. The moe
block's tree has the program's own structure, shapes and dtypes, and its
work is counted by hand."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, files, probe, program, weights
from tiny import load_data, tiny_cell

SPEC = files.load_spec()
DENSE = [files.load_config(SPEC, "musicgen-large"), load_data("phi3-medium-14b")]
IDS = [c["name"] for c in DENSE]
# the keys of "model" that ModelConfig took before it took all its fields
OLD_MODEL_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads",
                  "head_dim", "d_ff", "vocab", "mlp", "norm", "pos",
                  "rope_theta", "quant", "dtype", "kv_bits")


def old_model_config(cfg):
    from repro.configs.base import ModelConfig
    m = cfg["model"]
    return ModelConfig(name=cfg["name"], source=cfg["source"],
                       **{k: m[k] for k in OLD_MODEL_KEYS if k in m})


def old_params_fn(m):
    """The dense tree as the harness built it before block modules."""
    from repro.core.packed import PackedWeight

    def leaf_key(root, name, layer=None):
        key = jax.random.fold_in(root, np.uint32(zlib.crc32(name.encode())))
        return key if layer is None else jax.random.fold_in(key, layer)

    def norm_vector(root, name, layer, d):
        return 0.1 * jax.random.normal(leaf_key(root, name, layer), (d,),
                                       jnp.float32)

    def norm_names(prefix):
        return [f"{prefix}.scale"] + ([f"{prefix}.bias"]
                                      if m["norm"] == "layernorm" else [])

    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    shapes = {"attn.wq": (d, h * hd), "attn.wk": (d, kv * hd),
              "attn.wv": (d, kv * hd), "attn.wo": (h * hd, d),
              "ffn.w_up": (d, f), "ffn.w_down": (f, d)}
    if m["mlp"] in ("swiglu", "geglu"):
        shapes["ffn.w_gate"] = (d, f)

    def nest(flat):
        out = {}
        for path, leaf in flat.items():
            node = out
            *parents, last = path.split(".")
            for p in parents:
                node = node.setdefault(p, {})
            node[last] = leaf
        return out

    def build(root):
        layers = jnp.arange(m["n_layers"])
        flat = {}
        for name, (k, n) in shapes.items():
            words = jax.vmap(lambda l: jax.random.bits(
                leaf_key(root, name, l), (n, k // 32), jnp.uint32))(layers)
            flat[name] = PackedWeight(words, k=k, kind="dense",
                                      orig_dtype="float32")
        for name in norm_names("ln1") + norm_names("ln2"):
            flat[name] = jax.vmap(lambda l: norm_vector(root, name, l, d))(
                layers)
        top = {"embed": 0.02 * jax.random.normal(
                   leaf_key(root, "embed"), (m["vocab"], d), jnp.float32),
               "lm_head": 0.02 * jax.random.normal(
                   leaf_key(root, "lm_head"), (d, m["vocab"]), jnp.float32)}
        for name in norm_names("final_norm"):
            top[name] = norm_vector(root, name, None, d)
        return {**nest(top), "blocks": nest(flat)}
    return build


def old_work_of(m, w):
    gemm = costs.block_gemms(m, w.decoded, w.steps) + \
        costs.block_gemms(m, w.chunk_rows, w.chunks)
    attn = costs.attention(m, w.decode_keys, w.decoded, w.decode_keys) + \
        costs.attention(m, w.chunk_keys, w.chunk_rows, w.chunk_kv)
    head = costs.head(m, w.decoded, w.steps) + costs.head(m, w.firsts,
                                                          w.firsts)
    return {"gemm": gemm, "attn": attn, "head": head}


def _window_work():
    a = {"steps": 100, "running": {1: 10}, "admitting": {2: (1, 0)},
         "cached": {}}
    b = {"steps": 120, "running": {2: 5, 3: 2},
         "admitting": {4: (1, 128)}, "cached": {3: 0}}
    reqs = {1: {"plen": 200, "n": 30, "cached": 0},
            2: {"plen": 300, "n": 50}, 3: {"plen": 100, "n": 40},
            4: {"plen": 400, "n": 10}}
    return probe.window_work(a, b, reqs, delivered={1}, chunk=128)


@pytest.mark.parametrize("cfg", DENSE, ids=IDS)
def test_dense_model_config_is_the_old_one(cfg):
    assert files.load_block(cfg).__name__.endswith("dense")
    assert program.model_config(cfg) == old_model_config(cfg)


@pytest.mark.parametrize("cfg", DENSE, ids=IDS)
def test_dense_tree_is_bit_identical_to_the_old_builder(cfg):
    m = files.load_block(cfg).tiny(cfg["model"])
    root = weights.root_key(2**31 + 41)
    new = jax.jit(files.load_block(cfg).params_fn(m))(root)
    old = jax.jit(old_params_fn(m))(root)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cfg", DENSE, ids=IDS)
def test_dense_work_is_the_old_count(cfg):
    m, w = cfg["model"], _window_work()
    assert files.load_block(cfg).work_of(m, w) == old_work_of(m, w)


def test_moe_tree_is_the_programs():
    """Structure, shapes and dtypes of `freeze(init(key))` of the program's
    own moe model at the tiny cut."""
    from repro.core.packed import freeze_params
    from repro.models.api import get_model
    _, _, cfg, _ = tiny_cell("moe-window.long-prompts")
    model = get_model(program.model_config(cfg))
    want = jax.eval_shape(lambda k: freeze_params(model.init(k)),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(files.load_block(cfg).params_fn(cfg["model"]),
                         weights.root_key(5))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(got)] == \
        [(x.shape, x.dtype) for x in jax.tree.leaves(want)]
    m = cfg["model"]
    assert got["blocks"]["ffn"]["router"].shape == (2, m["d_model"], 8)
    assert got["blocks"]["ffn"]["experts"]["w_up"].packed.shape == \
        (2, 8, m["d_ff"], m["d_model"] // 32)


def test_moe_tiny_drops_no_token():
    m = dict(load_data("moe-window")["model"], n_experts=128, top_k=8)
    t = files.load_module("blocks", "moe").tiny(m)
    assert (t["n_experts"], t["top_k"]) == (16, 8)
    assert t["capacity_factor"] >= t["n_experts"] / t["top_k"]
    t = files.load_module("blocks", "moe").tiny(load_data("moe-window")
                                                ["model"])
    assert (t["n_experts"], t["top_k"], t["capacity_factor"]) == (8, 2, 4.0)


def test_moe_work_by_hand():
    m = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=32, vocab=100, mlp="swiglu", n_experts=8, top_k=2,
             local_window=4)
    # decodes: a 6-token prompt's tokens 1-3 (7, 8, 9 keys, 4 each in the
    # window), a 2-token prompt's tokens 1-2 (3, 4 keys); one chunk 0-5
    w = probe.WindowWork(steps=3, decoded=5, decode_keys=31, chunks=1,
                         chunk_rows=6, chunk_keys=21, chunk_kv=6, firsts=1,
                         decodes=[(6, 1, 4), (2, 1, 3)], spans=[(0, 6)])
    assert w.keys(0) == (31, 21, 6)
    assert w.keys(4) == (4 + 4 + 4 + 3 + 4, 1 + 2 + 3 + 4 + 4 + 4, 6)
    got = files.load_module("blocks", "moe").work_of(m, w)
    assert set(got) == {"gemm", "expert", "router", "attn", "head"}
    rows, L = 5 + 6, 2
    # attention projections: wq, wo 64x64, wk, wv 64x32; per row
    # K/8 + 2N bytes: 8 + 128, 8 + 64, 8 + 64, 8 + 128
    assert got["gemm"].ops == 2 * rows * 12288 * L
    assert got["gemm"].bytes == L * ((3 + 1) * 12288 / 8 + rows * 416)
    # 2 experts per token, each gate, up 64x32 and down 32x64; an expert
    # call reads top_k experts' weights
    assert got["expert"].ops == 2 * rows * 2 * 6144 * L
    assert got["expert"].bytes == L * ((3 + 1) * 2 * 6144 / 8
                                       + rows * 2 * (72 + 72 + 132))
    # fp32 router 64x8: weights once per call, bf16 rows in, fp32 out
    assert got["router"].ops == 2 * rows * 512 * L
    assert got["router"].bytes == L * ((3 + 1) * 512 * 4
                                       + rows * (64 * 2 + 8 * 4))
    # windowed keys: 19 decode, 18 chunk; 4 query heads x 16 dims
    assert got["attn"].ops == 4 * (19 + 18) * 4 * 16 * L
    assert got["attn"].bytes == L * ((19 + 6) * 2 * 16 / 8 * 2
                                     + rows * 4 * 16 * 2 * 2)
    assert got["head"] == costs.head(m, 5, 3) + costs.head(m, 1, 1)
