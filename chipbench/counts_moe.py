"""Operations and bytes the algorithm needs in a share of a DeepSeek-V2
style decoder (MLA attention, routed and shared experts), counted from the
configuration's shapes and the program's MoE counters.

As in ``counts``: a multiply-add is two operations, sizes are in elements,
``dtype_bytes`` turns them into bytes, and nothing depends on how the
program implements a step. Routed experts are counted per (token, expert)
pair actually routed to a held expert (``moe_pairs_*``), and their weights
per expert that got a pair in a step (``moe_expert_visits_decode``).
"""
from __future__ import annotations

import dataclasses

from chipbench.counts import Work


@dataclasses.dataclass(frozen=True)
class MlaMoe:
    """The shapes of an MLA + MoE decoder share that the counts need."""
    d: int
    layers: int
    first_dense: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    expert_ff: int
    router_experts: int
    n_shared: int
    vocab: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "MlaMoe":
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"],
                   first_dense=c["first_k_dense_replace"],
                   heads=c["num_attention_heads"], q_lora=c["q_lora_rank"],
                   kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   expert_ff=c["moe_intermediate_size"],
                   router_experts=c["router_experts"],
                   n_shared=c["n_shared_experts"], vocab=c["vocab_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    @property
    def mla_params(self) -> int:
        h = self.heads
        return (self.d * self.q_lora + self.q_lora * h * (self.nope + self.rope)
                + self.d * (self.kv_lora + self.rope)
                + self.kv_lora * h * (self.nope + self.v_dim)
                + h * self.v_dim * self.d)

    @property
    def expert_params(self) -> int:
        """Weights of one routed expert (gated: up, gate, down)."""
        return 3 * self.d * self.expert_ff

    @property
    def token_params(self) -> int:
        """Weights that multiply every token in all layers, routed experts
        and the output head left out."""
        dense = 3 * self.d * self.d_ff
        moe = self.expert_params * self.n_shared + self.d * self.router_experts
        return (self.layers * self.mla_params + self.first_dense * dense
                + self.moe_layers * moe)

    @property
    def latent(self) -> int:
        """Elements of one token's cache row per layer."""
        return self.kv_lora + self.rope


def attention_flops(m: MlaMoe, filled: float) -> float:
    """One query against ``filled`` positions in one layer, as the model
    states it: scores over qk_nope + qk_rope dims, values of v_head_dim."""
    return 2 * m.heads * (m.nope + m.rope + m.v_dim) * filled


def serve_call_flops(m: MlaMoe, batch: int, prompt: int, gen: int,
                     pairs_prefill: float, pairs_decode: float) -> float:
    """Model operations of one call: the prompt through every layer (causal
    attention), the head at its last position, then ``gen - 1`` decode steps
    of one token each over the filled positions; routed experts from the
    pairs the program computed."""
    per_token = 2 * m.token_params
    head = 2 * m.d * m.vocab
    expert = 2 * m.expert_params
    pairs = prompt * (prompt + 1) / 2
    prefill = (batch * prompt * per_token + batch * head
               + pairs_prefill * expert
               + batch * m.layers * attention_flops(m, 1) * pairs)
    filled = sum(prompt + i for i in range(1, gen))
    decode = ((gen - 1) * batch * (per_token + head) + pairs_decode * expert
              + batch * m.layers * attention_flops(m, filled))
    return prefill + decode


def mla_flash_attention(m: MlaMoe, batch: int, seq: int) -> Work:
    """Causal MLA over a prompt, all layers, as the model states it: query
    i attends keys 0..i with every head, scores over qk_nope + qk_rope dims
    and values of v_head_dim. Reads each head's q and k_nope, the shared
    k_rope and each head's v once, and writes each head's output once."""
    pairs = seq * (seq + 1) / 2
    flops = 2 * batch * m.heads * (m.nope + m.rope + m.v_dim) * pairs
    per_token = (m.heads * (m.nope + m.rope) + m.heads * m.nope + m.rope
                 + 2 * m.heads * m.v_dim)
    return Work(flops, batch * seq * per_token * m.dtype_bytes) * m.layers


def absorbed_decode_attention_call(m: MlaMoe, batch: int, prompt: int,
                                   gen: int) -> Work:
    """Every absorbed decode step of one call, all layers: each of the
    heads scores the latent rows (kv_lora + rope) of the filled positions
    and sums their kv_lora part; the latent rows are read once."""
    filled = sum(prompt + i for i in range(1, gen))
    flops = 2 * batch * m.heads * filled * (m.latent + m.kv_lora)
    elems = batch * filled * m.latent
    return Work(flops, elems * m.dtype_bytes) * m.layers


def decode_experts(m: MlaMoe, pairs: float, visits: float) -> Work:
    """The routed experts' matmuls of decode: each pair through one expert,
    each visited expert's weights read once."""
    return Work(2 * m.expert_params * pairs,
                m.expert_params * m.dtype_bytes * visits)
