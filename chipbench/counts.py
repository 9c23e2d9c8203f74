"""Operations and bytes the algorithm needs, counted from a configuration's
shapes. The counts do not depend on how the program implements a step: a
kernel that pads, transposes or walks empty cache slots does more work than
is counted here, and its roofline share shows it.

A multiply-add is two operations. Sizes are in elements; ``dtype_bytes``
turns them into bytes.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Decoder:
    """The shapes of a dense decoder that the counts need."""
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Decoder":
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=c["hidden_act"] == "silu")

    @property
    def layer_matmul_params(self) -> int:
        """Weights that multiply each token in one layer."""
        hd, kd = self.heads * self.head_dim, self.kv_heads * self.head_dim
        mlp = (3 if self.gated else 2) * self.d * self.d_ff
        return self.d * hd + 2 * self.d * kd + hd * self.d + mlp


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def seconds(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """Least time the chip could take: the larger of the two bounds."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


def flash_attention(m: Decoder, batch: int, seq: int) -> Work:
    """Causal self-attention over a prompt, all layers: query i attends
    keys 0..i. Reads Q, K, V once and writes O once."""
    pairs = seq * (seq + 1) / 2
    flops = 2 * 2 * batch * m.heads * m.head_dim * pairs
    elems = batch * seq * (2 * m.heads + 2 * m.kv_heads) * m.head_dim
    return Work(flops, elems * m.dtype_bytes) * m.layers


def decode_attention_step(m: Decoder, batch: int, filled: int) -> Work:
    """One query token per sequence against ``filled`` cached positions,
    all layers: reads K and V of the filled positions and q, writes o."""
    flops = 2 * 2 * batch * m.heads * m.head_dim * filled
    elems = (2 * batch * filled * m.kv_heads * m.head_dim
             + 2 * batch * m.heads * m.head_dim)
    return Work(flops, elems * m.dtype_bytes) * m.layers


def decode_attention_call(m: Decoder, batch: int, prompt: int,
                          gen: int) -> Work:
    """Every decode step of one greedy call: the step that feeds token i
    (i = 1..gen-1) writes position prompt + i - 1 and attends to
    prompt + i positions."""
    w = Work()
    for i in range(1, gen):
        w = w + decode_attention_step(m, batch, prompt + i)
    return w


def serve_call_flops(m: Decoder, batch: int, prompt: int, gen: int) -> float:
    """Model operations of one call: the prompt through every layer, the
    output head at the prompt's last position, then ``gen - 1`` decode
    steps of one token each, attention counted over filled positions."""
    per_token = 2 * m.layer_matmul_params * m.layers
    head = 2 * m.d * m.vocab
    prefill = (batch * prompt * per_token + batch * head
               + flash_attention(m, batch, prompt).flops)
    decode = ((gen - 1) * batch * (per_token + head)
              + decode_attention_call(m, batch, prompt, gen).flops)
    return prefill + decode
