"""The packed Pallas kernels compile for a TPU v5e at real serving widths.

Compiles (never runs) each kernel with `interpret=False` for a described
`v5e:2x2` topology, with the block sizes the TPU heuristic hands out when
no tuned cache exists (`tune.TPU_*`). Interpret mode accepts tiles and
kernel bodies the TPU compiler refuses (unaligned blocks, unsigned
reductions, value-level dynamic slices); this file is what catches them
without a chip. The topology is described inside a fixture: only the
worker that runs these tests loads the TPU compiler library.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bitpack import packed_width
from repro.kernels import tune
from repro.kernels.binary_gemm import (
    binary_gemm_vpu, binary_gemm_vpu_packed, binary_gemm_vpu_packed_io,
)
from repro.kernels.decode_attention import (
    decode_attention_packed, decode_attention_packed_paged,
)
from repro.kernels.prefill_attention import (
    prefill_attention_packed, prefill_attention_packed_paged,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # pragma: no cover - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep it out of the way
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, name, *shapes):
    """Compile for the described chip; the kernel is a custom call that
    carries its `name`, which is how a device trace shows it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}[.0-9]* = .*custom-call", text), name


SLOTS = 8                                   # decode rows (serving slots)


@pytest.mark.parametrize("d_model", [2048, 5120, 8192])
@pytest.mark.parametrize("kernel", ["vpu", "vpu_packed", "packed_io_bits",
                                    "packed_io_f32"])
def test_binary_gemm_compiles(one_chip, kernel, d_model):
    """A decode-sized FFN up-projection (SLOTS x d_model -> 4 d_model)."""
    m, k, n = SLOTS, d_model, 4 * d_model
    kw = packed_width(k)
    w = ((n, kw), jnp.uint32)
    if kernel == "vpu":
        fn = functools.partial(binary_gemm_vpu, k_true=k, interpret=False,
                               **tune.TPU_GEMM_TILE)
        _compile(fn, one_chip, "binary_gemm_vpu", ((m, kw), jnp.uint32), w)
    elif kernel == "vpu_packed":
        fn = functools.partial(binary_gemm_vpu_packed, k_true=k,
                               interpret=False, **tune.TPU_GEMM_TILE)
        _compile(fn, one_chip, "binary_gemm_vpu", ((m, k), jnp.bfloat16), w)
    else:
        lhs = ((m, kw), jnp.uint32) if kernel == "packed_io_bits" \
            else ((m, k), jnp.bfloat16)
        fn = functools.partial(binary_gemm_vpu_packed_io, k_true=k,
                               interpret=False, **tune.TPU_FUSED_TILE)
        _compile(fn, one_chip, "binary_gemm_fused", lhs, w,
                 ((n,), jnp.int32), ((n,), jnp.int32))


# (kv heads, GQA group, head_dim): musicgen-large MHA, and a GQA model
ATTN = {64: (32, 1), 128: (8, 4)}
T, PAGE, CHUNK = 2048, 128, 128


POOL = SLOTS * T // PAGE                     # pages: every slot at T
ATTN_CASES = [(kernel, hd, POOL) for hd in sorted(ATTN)
              for kernel in ("decode", "decode_paged", "prefill",
                             "prefill_paged")] + \
    [(kernel, 64, 16384) for kernel in ("decode_paged", "prefill_paged")]


@pytest.mark.parametrize("kernel,hd,pool", ATTN_CASES)
def test_packed_attention_compiles(one_chip, kernel, hd, pool):
    """At the smoke's pool, and for the paged kernels at 16384 pages of 128
    positions — more than a chip's HBM holds at musicgen-large's depth:
    they DMA only the pages a row addresses, so VMEM never holds the
    pool."""
    hkv, g = ATTN[hd]
    hdw = packed_width(hd)
    b, s = (SLOTS, 1) if kernel.startswith("decode") else (1, CHUNK)
    blocks = dict(tune.TPU_DECODE_BLOCKS if s == 1 else
                  tune.TPU_PREFILL_BLOCKS)
    q = ((b, s, hkv * g, hd), jnp.bfloat16)
    vs = ((b, hkv), jnp.float32)
    lens = ((b,), jnp.int32)
    if kernel.endswith("paged"):
        pages = ((pool, PAGE, hkv, hdw), jnp.uint32)
        pt = ((b, T // PAGE), jnp.int32)
        operands = [q, pages, pages, vs, pt, lens]
        fn = decode_attention_packed_paged if s == 1 \
            else prefill_attention_packed_paged
    else:
        cache = ((b, T, hkv, hdw), jnp.uint32)
        operands = [q, cache, cache, vs, lens]
        fn = decode_attention_packed if s == 1 else prefill_attention_packed
    if s > 1:
        operands.append(lens)                         # q_pos
    name = "packed_attention_paged" if kernel.endswith("paged") \
        else "packed_attention"
    _compile(functools.partial(fn, route="pallas", interpret=False, **blocks),
             one_chip, name, *operands)


# musicgen-large's serving cell: 64 slots, 32 kv heads of 64, a pool of 288
# pages of 128, tables of 16 pages; decode and a 128-row chunk
CELL_A = dict(slots=64, hkv=32, hd=64, pool=288)


@pytest.mark.parametrize("kernel", ["decode_paged", "prefill_paged"])
def test_paged_attention_compiles_at_the_cells_shape(one_chip, kernel):
    """At the largest block_b the tuner tries: the head block keeps the
    kernel's VMEM panels and blocks within the scoped limit."""
    hkv, hd = CELL_A["hkv"], CELL_A["hd"]
    hdw = packed_width(hd)
    b, s = (CELL_A["slots"], 1) if kernel == "decode_paged" else (1, CHUNK)
    blocks = dict(tune.TPU_DECODE_BLOCKS if s == 1 else
                  tune.TPU_PREFILL_BLOCKS, block_b=max(tune.DECODE_BLOCK_B))
    pages = ((CELL_A["pool"], PAGE, hkv, hdw), jnp.uint32)
    lens = ((b,), jnp.int32)
    operands = [((b, s, hkv, hd), jnp.bfloat16), pages, pages,
                ((b, hkv), jnp.float32), ((b, T // PAGE), jnp.int32), lens]
    fn = decode_attention_packed_paged
    if s > 1:
        operands.append(lens)                         # q_pos
        fn = prefill_attention_packed_paged
    _compile(functools.partial(fn, route="pallas", interpret=False, **blocks),
             one_chip, "packed_attention_paged", *operands)
