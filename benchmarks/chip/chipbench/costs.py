"""Operations and bytes of the model's work, from shapes alone.

The same count whatever implements it: operations are the +-1 MACs of each
logical GEMM or attention call, times 2; bytes are what an ideal kernel
must move: packed weights or 1-bit K/V pages read once per call, packed
sign bits in, bf16 values out. Rows are the logical rows (active decode
rows, valid prompt tokens), never padding. Every operation is held
against the chip's int8 peak: +-1 products are exact there, so no
implementation can read above 100%.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"
ACT_BYTES = 2            # bf16 activations out of a call


@dataclasses.dataclass
class Work:
    ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.ops + o.ops, self.bytes + o.bytes)

    def scale(self, f: float) -> "Work":
        return Work(self.ops * f, self.bytes * f)


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def least_time(work: Work, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of ops at the int8 peak and bytes at
    the memory bandwidth, and which of the two it was."""
    t_ops = work.ops / peak["int8_ops_per_s"]
    t_mem = work.bytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def attn_gemm_shapes(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each binary attention GEMM of one block, in call order."""
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d)]


def ffn_gemm_shapes(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each binary GEMM of one FFN (or one expert), in call
    order."""
    d, f = m["d_model"], m["d_ff"]
    gate = [(d, f)] if m["mlp"] in ("swiglu", "geglu") else []
    return gate + [(d, f), (f, d)]


def gemm_shapes(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each binary GEMM of one dense block, in call order."""
    return attn_gemm_shapes(m) + ffn_gemm_shapes(m)


def block_gemms(m: dict, rows: float, calls: float = 1.0,
                shapes: list[tuple[int, int]] | None = None) -> Work:
    """Every layer's GEMMs (`shapes`, by default all of a dense block's)
    for `calls` calls that together carry `rows` rows: the weights are
    read once per call."""
    w = Work()
    for k, n in gemm_shapes(m) if shapes is None else shapes:
        w = w + Work(2.0 * rows * k * n,
                     calls * n * k / 8 + rows * (k / 8 + n * ACT_BYTES))
    return w.scale(m["n_layers"])


def attention(m: dict, key_sum: float, queries: float,
              kv_read: float) -> Work:
    """Packed attention over every layer: `key_sum` (query, key) pairs
    scored and summed, `queries` query rows in and out, `kv_read` key
    positions whose K and V sign bits are read."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ops = 4.0 * key_sum * hq * hd           # scores and V sums, MACs x 2
    byt = kv_read * hkv * hd / 8 * 2 + queries * hq * hd * ACT_BYTES * 2
    return Work(ops, byt).scale(m["n_layers"])


def head(m: dict, rows: float, calls: float = 1.0) -> Work:
    """The output head: (rows, d) x (d, vocab), bf16 weights read once."""
    d, v = m["d_model"], m["vocab"]
    return Work(2.0 * rows * d * v, calls * d * v * ACT_BYTES
                + rows * (d + v) * ACT_BYTES)


def _sum_capped(a: int, b: int, window: int) -> int:
    """Sum of min(v, window) over v = a..b; window 0 caps nothing."""
    over = 0                                   # values above the cap
    if window:
        over = max(0, b - max(a, window + 1) + 1)
        b = min(b, window)
    below = (a + b) * (b - a + 1) // 2 if b >= a else 0
    return below + window * over


def decode_keys(prompt_len: int, first: int, last: int,
                window: int = 0) -> int:
    """Keys the decode steps producing tokens first..last-1 attend: token
    j of a request with an L-token prompt attends L + j positions, at
    most `window` of them where the layer has a window."""
    return _sum_capped(prompt_len + first, prompt_len + last - 1, window)


def chunk_keys(lo: int, hi: int, window: int = 0) -> int:
    """Keys a prefill chunk of positions lo..hi-1 attends (causal): the
    query at position p attends p + 1 keys, at most `window`."""
    return _sum_capped(lo + 1, hi, window)


def chunk_reads(lo: int, hi: int, window: int = 0) -> int:
    """Key positions whose K and V a prefill chunk of positions lo..hi-1
    reads: every one below hi, or with a window those from the first
    query's window on."""
    return hi - (max(0, lo - window + 1) if window else 0)
