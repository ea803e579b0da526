"""The work a step or a kernel call needs, counted from real token counts
and context lengths: never from a kernel's grid or block sizes, so the
yardstick stays put when a kernel changes.

Shapes come from the configuration file (its published keys, and the
served ``lora`` group); a float32 model moves 4 bytes an element."""

from __future__ import annotations

import dataclasses
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    lora_rank: int
    lora_targets: tuple[str, ...]
    itemsize: int = 4

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        m = cfg
        lora = cfg["serving"]["lora"]
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   lora_rank=lora["rank"], lora_targets=tuple(lora["targets"]),
                   itemsize=4 if cfg["serving"]["dtype"] == "float32" else 2)

    def proj_dims(self) -> dict[str, tuple[int, int]]:
        d, hd = self.d_model, self.head_dim
        return {"q": (d, self.heads * hd), "k": (d, self.kv_heads * hd),
                "v": (d, self.kv_heads * hd), "o": (self.heads * hd, d)}


def token_flops(s: Shape, position: int, emits: bool) -> int:
    """Model FLOPs of one computed token at absolute ``position`` (it
    attends ``position + 1`` keys): projections, LoRA, MLP and attention in
    every layer, and the LM head when the token's logits are used."""
    per_layer = 0
    for t, (din, dout) in s.proj_dims().items():
        per_layer += 2 * din * dout
        if t in s.lora_targets:
            per_layer += 2 * din * s.lora_rank + 2 * s.lora_rank * dout
    per_layer += 3 * 2 * s.d_model * s.d_ff
    per_layer += 4 * s.heads * s.head_dim * (position + 1)
    head = 2 * s.d_model * s.vocab if emits else 0
    return s.layers * per_layer + head


def span_flops(s: Shape, start: int, n: int, emits_last: bool) -> int:
    """FLOPs of ``n`` tokens computed at positions ``start .. start+n-1``
    (closed form of summing :func:`token_flops`)."""
    if n <= 0:
        return 0
    f0 = token_flops(s, 0, False)  # the position-independent part
    attn = 4 * s.heads * s.head_dim * s.layers
    # sum over positions p of (p + 1) = n*start + n(n+1)/2
    ctx = n * start + n * (n + 1) // 2
    head = 2 * s.d_model * s.vocab if emits_last else 0
    return n * (f0 - attn) + attn * ctx + head


def ragged_extend_need(s: Shape, rows: Iterable[tuple[int, int]]) -> tuple[int, int]:
    """(FLOPs, bytes) one ``ragged_extend`` call needs in one layer.

    ``rows`` are (start, n): a row's n real query tokens at positions
    ``start ..``; each attends every earlier position and itself. Bytes: the
    real queries in and out once, and each row's keys and values once."""
    flops = nbytes = 0
    for start, n in rows:
        if n <= 0:
            continue
        ctx = n * start + n * (n + 1) // 2
        flops += 4 * s.heads * s.head_dim * ctx
        nbytes += 2 * n * s.heads * s.head_dim * s.itemsize
        nbytes += 2 * (start + n) * s.kv_heads * s.head_dim * s.itemsize
    return flops, nbytes


def fused_sgmv_need(s: Shape, target: str, tokens: int,
                    adapters: int) -> tuple[int, int]:
    """(FLOPs, bytes) one ``fused_sgmv`` call needs: ``tokens`` real rows
    times the base weight plus each row's adapter, reading the weight once,
    each distinct adapter once, and the real rows in and out once."""
    if tokens <= 0:
        return 0, 0
    din, dout = s.proj_dims()[target]
    r = s.lora_rank
    flops = 2 * tokens * din * dout + 2 * tokens * (din * r + r * dout)
    nbytes = (din * dout + tokens * (din + dout)
              + adapters * (din * r + r * dout)) * s.itemsize
    return flops, nbytes
