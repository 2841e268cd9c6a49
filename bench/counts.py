"""Operations and bytes the served work needs, from shapes alone.

These are the benchmark's own counts (a later change to the program cannot
move them): the forward FLOPs of a token through one member, the attention
FLOPs over a context, and the FLOPs and bytes one call of the paged decode
kernel needs for the slots that are active in it.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

BF16 = 2  # bytes per element of weights, activations and the KV pool


def heads(m: dict):
    H, K = m["n_heads"], m["n_kv_heads"]
    return H, K, (m["head_dim"] or m["d_model"] // H)


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    D, F = m["d_model"], m["d_ff"]
    H, K, hd = heads(m)
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def token_flops(m: dict, *, head: bool) -> int:
    """Matmul FLOPs of one token through one member (2 per multiply-add),
    without attention over the context; ``head`` adds the vocabulary
    projection (decode tokens need it, prefill tokens do not)."""
    n = m["n_layers"] * layer_matmul_params(m)
    if head:
        n += m["d_model"] * m["vocab_size"]
    return 2 * n


def attention_flops(m: dict, context: int) -> int:
    """QK and PV FLOPs of one query token over ``context`` keys, all layers."""
    H, _, hd = heads(m)
    return 4 * context * H * hd * m["n_layers"]


def prefill_chunk_flops(m: dict, start: int, n: int) -> int:
    """A chunk of ``n`` prompt tokens at positions [start, start + n): each
    attends causally over its own prefix."""
    ctx = n * start + n * (n + 1) // 2
    return n * token_flops(m, head=False) + attention_flops(m, 1) * ctx


def paged_decode_call(m: dict, members: int, lens: Iterable[int]):
    """(FLOPs, bytes) one call of the paged decode kernel needs: one query
    per active slot and member attending over its ``len`` cached rows in
    every layer.  Bytes: the K and V rows read, the queries read and the
    outputs written."""
    H, K, hd = heads(m)
    lens = list(lens)
    rows = sum(lens)
    per_layer_flops = 4 * rows * H * hd
    per_layer_bytes = BF16 * (2 * rows * K * hd + 2 * len(lens) * H * hd)
    n = members * m["n_layers"]
    return n * per_layer_flops, n * per_layer_bytes


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
