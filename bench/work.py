"""Operations and bytes that the served model's algorithm needs, from shapes.

These are the numerators of the roofline shares and of ``mfu``. They count
what the computation requires, not what an implementation happens to do:
every weight matrix and the head are read once per program call; a decode
step reads the keys and values of each active slot's real context (idle
lanes and positions at or past a slot's length are not counted); a prefill
counts the true prompt length (not its compile bucket) and the head at the
last position only. A program that skips wasted work can therefore raise
its share, but can never pass 100%.

Shapes come from a configuration file of ``bench/configs`` (Hugging Face
key names). Matmul operations count 2 per multiply-add.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Mapping, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peak: Mapping[str, float]
                 ) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


class DenseDecoder:
    """A pre-norm decoder with grouped-query attention and a gated MLP."""

    def __init__(self, cfg: Mapping):
        self.d = cfg["hidden_size"]
        self.f = cfg["intermediate_size"]
        self.h = cfg["num_attention_heads"]
        self.kh = cfg["num_key_value_heads"]
        self.dh = cfg["head_dim"]
        self.v = cfg["vocab_size"]
        self.layers = cfg["num_hidden_layers"]
        self.wbytes = DTYPE_BYTES[cfg["torch_dtype"]]
        d, h, kh, dh = self.d, self.h, self.kh, self.dh
        self.layer_params = d * h * dh * 2 + d * kh * dh * 2 + 3 * d * self.f
        self.matmul_params = self.layers * self.layer_params + d * self.v

    def _attn_flops(self, ctx_sum: int) -> int:
        # scores and weighted values, every query head over its context
        return 4 * self.layers * self.h * self.dh * ctx_sum

    def _kv_bytes(self, tokens: int) -> int:
        return 2 * self.layers * self.kh * self.dh * tokens * self.wbytes

    def decode_step(self, contexts: Iterable[int]) -> Tuple[float, float]:
        """(operations, bytes) of one decode step that adds one token to
        each active slot; ``contexts`` are the slots' lengths including the
        new token."""
        ctx = list(contexts)
        b = len(ctx)
        if b == 0:
            return 0.0, 0.0
        flops = 2 * b * self.matmul_params + self._attn_flops(sum(ctx))
        nbytes = (self.matmul_params * self.wbytes          # weights + head
                  + b * self.d * self.wbytes                # embedding rows
                  + self._kv_bytes(sum(ctx) - b)            # cached context
                  + self._kv_bytes(b))                      # new keys/values
        return float(flops), float(nbytes)

    def prefill(self, n: int) -> Tuple[float, float]:
        """(operations, bytes) of prefilling an ``n``-token prompt: causal
        attention, the head at the last position only."""
        flops = (2 * n * self.layers * self.layer_params + 2 * self.d * self.v
                 + self._attn_flops(n * (n + 1) // 2))
        nbytes = (self.matmul_params * self.wbytes
                  + n * self.d * self.wbytes
                  + self._kv_bytes(n))
        return float(flops), float(nbytes)
