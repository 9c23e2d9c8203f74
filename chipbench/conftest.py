"""Tiny sizes of the ``serve_moe`` kind for the harness's CPU tests
(``python -m pytest chipbench/tests``): ``tests/helpers.tiny_copy`` cuts
every workload by its kind, and ``tests/test_serve_moe.tiny_moe_copy``
cuts every configuration whose reference is ``mla_moe_decoder`` after it.

The check at these sizes: over 18 seeds on the CPU the program's share of
served positions more than 0.02 below the reference's best was 0 to 0.0625,
the float8 control's 0.219 to 0.625, and the three faults that
``tests/test_serve_moe.py`` plants read 0.156 to 0.531 over 6 of them."""
from chipbench.tests import helpers

TINY_MLA_MOE = dict(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    router_experts=16, n_routed_experts=4, first_local_expert=0, n_group=4,
    topk_group=2, num_experts_per_tok=4, n_shared_experts=2, vocab_size=256)

helpers.TINY_TRAFFIC.setdefault(
    "serve_moe", {"batch": 4, "prompt": 24, "gen": 8, "pool": 2})
helpers.TINY_LIMITS.setdefault("serve_moe", {"served_token_miss_share": 0.1})
TINY_MOE_CHECK = {"gap_tolerance": 0.02}
