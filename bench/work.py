"""The work a step needs, and the chip's peaks: the benchmark's yardstick.

Everything here is arithmetic on shapes and token counts. It counts what
attention and the model *need*, not what today's kernels happen to move, so
a kernel that reads less (one pass over K, a shared prefix read once) gets
closer to its roofline instead of past it:

* decode attention, per step and layer: every distinct live KV token read
  once (positions that several slots share through a common prompt prefix
  count once), plus each slot's q row read and output row written;
  4 * ctx * H * Dh FLOPs per slot (QK^T and PV);
* prefill attention, per chunk of rows [start, start + n) of one request:
  the request's KV window read once, q and output rows, and
  4 * H * Dh * sum(row + 1) FLOPs under the causal mask;
* the model, per token: 2 FLOPs per weight of every matmul, attention over
  the live context, and the LM head only where a token is sampled.

``peaks`` reads ``peaks.json``, one row per ``device_kind`` with its
source; a device that is not in the table is an error.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Sequence

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"

# bytes per element of the served activations (q rows, attention outputs)
ACT_BYTES = 2
KV_BYTES = {"bf16": 2, "fp16": 2, "fp32": 4, "int8": 1, "int4": 0.5}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in {path.name}; "
                            f"known: {sorted(table)}")
    return table[device_kind]


@dataclass(frozen=True)
class Shape:
    """The sizes the arithmetic needs, from a configuration file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_dtype: str = "bf16"

    @classmethod
    def from_config(cls, conf: dict) -> "Shape":
        return cls(
            layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
            heads=conf["num_attention_heads"], kv_heads=conf["num_key_value_heads"],
            head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
            vocab=conf["vocab_size"], kv_dtype=conf["serving"]["kv_dtype"],
        )

    @property
    def kv_token_bytes_per_layer(self) -> float:
        """K and V of one token in one layer."""
        return 2 * self.kv_heads * self.head_dim * KV_BYTES[self.kv_dtype]

    @property
    def matmul_weights_per_layer(self) -> int:
        d, hd = self.d_model, self.heads * self.head_dim
        kvd = self.kv_heads * self.head_dim
        return d * hd + 2 * d * kvd + hd * d + 3 * d * self.d_ff


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_time(self, peak: dict) -> tuple[float, str]:
        """(seconds, bound): the larger of FLOPs over peak FLOP/s and bytes
        over peak bandwidth, and which of the two it was."""
        tc = self.flops / peak["bf16_flops_per_s"]
        tm = self.bytes / peak["hbm_bytes_per_s"]
        return (tc, "compute") if tc >= tm else (tm, "memory")


def distinct_prefix_tokens(seqs: Sequence[Sequence[int]]) -> int:
    """Distinct token positions over ``seqs``, where a position of two
    sequences is one position when both agree on every token up to and
    including it (the nodes of their prefix trie). Sorted lexicographically,
    that is the summed lengths less each neighbour pair's common prefix."""
    arrs = sorted((np.asarray(s, np.int64) for s in seqs), key=lambda a: tuple(a.tolist()))
    total = sum(len(a) for a in arrs)
    for a, b in zip(arrs, arrs[1:]):
        n = min(len(a), len(b))
        neq = np.nonzero(a[:n] != b[:n])[0]
        total -= int(neq[0]) if len(neq) else n
    return total


def decode_step_work(shape: Shape, prompts: Sequence[Sequence[int]],
                     contexts: Sequence[int], shared: int | None = None) -> Work:
    """One decode step over all layers. ``prompts[i]`` is slot i's prompt
    tokens and ``contexts[i]`` the tokens it attends over this step (prompt,
    tokens generated so far and the new one). Prompt positions shared
    between slots are read once; generated tokens are each slot's own.
    ``shared`` is ``distinct_prefix_tokens(prompts)``, where the caller
    already has it."""
    if shared is None:
        shared = distinct_prefix_tokens(prompts)
    distinct = shared + sum(c - len(p) for p, c in zip(prompts, contexts))
    hd = shape.heads * shape.head_dim
    bytes_ = distinct * shape.kv_token_bytes_per_layer + len(contexts) * 2 * hd * ACT_BYTES
    flops = 4.0 * hd * float(sum(contexts))
    return Work(flops * shape.layers, bytes_ * shape.layers)


def prefill_chunk_work(shape: Shape, start: int, n: int) -> Work:
    """Attention of rows [start, start + n) of one request, all layers."""
    hd = shape.heads * shape.head_dim
    rows_ctx = n * start + n * (n + 1) / 2  # sum of (row + 1)
    bytes_ = (start + n) * shape.kv_token_bytes_per_layer + n * 2 * hd * ACT_BYTES
    return Work(4.0 * hd * rows_ctx * shape.layers, bytes_ * shape.layers)


def model_flops(shape: Shape, ctx_sum: float, tokens: int, sampled: int) -> float:
    """Model FLOPs of ``tokens`` token rows whose attention contexts sum to
    ``ctx_sum``, of which ``sampled`` rows go through the LM head."""
    dense = 2.0 * shape.matmul_weights_per_layer * shape.layers * tokens
    attn = 4.0 * shape.heads * shape.head_dim * ctx_sum * shape.layers
    head = 2.0 * shape.d_model * shape.vocab * sampled
    return dense + attn + head
