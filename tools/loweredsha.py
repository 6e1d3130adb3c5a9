"""SHA-256 of the lowered text of GPT-2's, DeepSeek-V2's, dots3-note-prev's,
Olmo-Hybrid's, SmallThinker's and MiniCPM-SALA's serving programs at toy
widths, on the CPU:
the paged decode step and the prefill buckets of each (DeepSeek-V2's buckets
cover both forms of its latent attention; dots3-note-prev's decode step and
one prefill program run both page groups, the selection and the sigmoid
router; Olmo-Hybrid's carry slot state beside the pools; SmallThinker's hold
every expert, so its expert layers build no branch over the sorted pairs;
MiniCPM-SALA's decode step scores, selects and reads a table of pages, and
its one prefill program passes the dense length). A PR
that says "their programs are the parent's" shows it with these: the same
hashes from the parent's tree and from its own
(``tests/test_lowered_text_guard.py`` holds the parent's). The text is what
jax lowers before XLA sees it, so the hashes hold for one jax version and
say nothing about another backend's compile.

    JAX_PLATFORMS=cpu python tools/loweredsha.py        # one JSON object
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DEEPSEEK_V2_TOY = dict(
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24, kv_lora_rank=32,
    rms_norm_eps=1e-6, rope_theta=10000, n_layer=3, first_k_dense_replace=1,
    intermediate_size=96, moe_intermediate_size=24, n_shared_experts=2,
    n_routed_experts=16, n_group=4, topk_group=2, num_experts_per_tok=3,
    norm_topk_prob=False, routed_scaling_factor=16, n_vocab=200,
    initializer_range=0.02, max_position_embeddings=256,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    held_experts=[0, 1, 5, 9], precision={"weights": "float32"},
    engine={"batch_size": 4, "paged": True, "page_size": 8, "num_pages": 64,
            "max_length": 128, "cache_dtype": "float32",
            "prefill_buckets": [8, 16, 32, 64]})
DOTS3_NOTE_TOY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=80000000, attention_gate_type="headwise",
    swa_num_attention_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=40,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=50000, swa_attention_gate_type="headwise",
    sliding_window_size=5, index_n_heads=4, index_head_dim=16, index_topk=8,
    apply_mla_qkv_lora_rescale=True, rms_norm_eps=1e-5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention"], n_layer=4, first_k_dense_replace=1,
    n_shared_experts=1, n_routed_experts=16, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=1, n_vocab=200,
    initializer_range=0.02, max_position_embeddings=256,
    held_experts=[0, 1, 2, 5, 9, 14], precision={"weights": "float32"},
    engine={"batch_size": 3, "paged": True, "page_size": 2,
            "num_pages": {"all": 90, "window": 20}, "max_length": 64,
            "cache_dtype": "float32", "prefill_buckets": [16]})
OLMO_HYBRID_TOY = dict(
    hidden_size=32, intermediate_size=48, num_attention_heads=2,
    num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6, n_layer=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=64, linear_conv_kernel_dim=4, n_vocab=200,
    initializer_range=0.1, max_position_embeddings=128,
    decay_init={"A_log_mean": 0.0, "A_log_std": 0.5, "dt_bias_mean": -3.0,
                "dt_bias_std": 0.7, "a_proj_std": 0.02, "conv_std": 0.3},
    precision={"weights": "float32"},
    engine={"batch_size": 3, "paged": True, "page_size": 4,
            "num_pages": {"all": 64}, "max_length": 64,
            "cache_dtype": "float32", "prefill_buckets": [16]})
SMALLTHINKER_TOY = dict(
    model="smallthinker", hidden_size=64, num_attention_heads=6,
    num_key_value_heads=2, head_dim=16, rope_theta=1500000,
    sliding_window_size=5, rms_norm_eps=1e-6, n_layer=4,
    moe_ffn_hidden_size=24, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], n_vocab=200, initializer_range=0.1,
    max_position_embeddings=256, held_experts=list(range(8)),
    precision={"weights": "float32"},
    engine={"batch_size": 3, "paged": True, "page_size": 4,
            "num_pages": {"all": 64, "window": 12}, "max_length": 64,
            "cache_dtype": "float32", "prefill_buckets": [16]})
MINICPM_SALA_TOY = dict(
    model="minicpm_sala", hidden_size=32, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=1, head_dim=8,
    rms_norm_eps=1e-6, n_layer=4, num_hidden_layers=32,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    lightning_nh=2, lightning_nkv=2, lightning_head_dim=8, n_vocab=200,
    max_position_embeddings=128, rope_theta=10000, scale_emb=1,
    scale_depth=1.4, dim_model_base=32,
    sparse_config=dict(kernel_size=2, kernel_stride=1, block_size=4, topk=5,
                       init_blocks=1, window_size=4, dense_len=8),
    initializer_range=0.1, precision={"weights": "float32"},
    engine={"batch_size": 3, "paged": True, "page_size": 4,
            "num_pages": {"all": 120}, "max_length": 128,
            "cache_dtype": "float32", "prefill_buckets": [64]})
GPT2_TOY = dict(n_layer=2, n_embd=32, n_head=2, n_ctx=64, n_vocab=64,
                engine={"batch_size": 2, "paged": True, "page_size": 8,
                        "max_length": 64, "prefill_buckets": [8, 16]})


def engines():
    """{model: its toy paged engine}, seeded weights."""
    import numpy as np

    from benchmark.reference import deepseek_v2 as ref_v2
    from benchmark.reference import minicpm_sala as ref_sala
    from benchmark.reference import dots3_note as ref_dots3
    from benchmark.reference import olmo_hybrid as ref_olmo
    from benchmark.reference import smallthinker as ref_small
    from benchmark.systems import deepseek_v2 as adaptor_v2
    from benchmark.systems import minicpm_sala as adaptor_sala
    from benchmark.systems import dots3_note as adaptor_dots3
    from benchmark.systems import olmo_hybrid as adaptor_olmo
    from benchmark.systems import smallthinker as adaptor_small
    from benchmark.weights import make_weights
    from mxnet_tpu import nd
    from mxnet_tpu.inference import GenerationEngine
    from mxnet_tpu.models import gpt2

    c = GPT2_TOY
    net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=c["n_layer"],
                        units=c["n_embd"], num_heads=c["n_head"],
                        max_length=c["n_ctx"], vocab_size=c["n_vocab"])
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))   # shapes, then parameters
    weights = make_weights(ref_v2.param_specs(DEEPSEEK_V2_TOY), 7)
    dots3 = make_weights(ref_dots3.param_specs(DOTS3_NOTE_TOY), 7)
    olmo = make_weights(ref_olmo.param_specs(OLMO_HYBRID_TOY), 7)
    small = make_weights(ref_small.param_specs(SMALLTHINKER_TOY), 7)
    sala = make_weights(ref_sala.param_specs(MINICPM_SALA_TOY), 7)
    # a later model is built LAST: the blocks' names count up as they are made
    return {"gpt2": GenerationEngine(net, **c["engine"]),
            "deepseek_v2": adaptor_v2.build_serve(DEEPSEEK_V2_TOY, weights)[0],
            "dots3_note": adaptor_dots3.build_serve(DOTS3_NOTE_TOY, dots3)[0],
            "olmo_hybrid": adaptor_olmo.build_serve(OLMO_HYBRID_TOY, olmo)[0],
            "smallthinker": adaptor_small.build_serve(SMALLTHINKER_TOY, small)[0],
            "minicpm_sala": adaptor_sala.build_serve(MINICPM_SALA_TOY, sala)[0]}


def lowered_sha():
    """{"<model>.decode" | "<model>.prefill<bucket>": SHA-256 of the text}."""
    sha = lambda lowered: hashlib.sha256(  # noqa: E731
        lowered.as_text().encode()).hexdigest()
    out = {}
    for model, engine in engines().items():
        out[f"{model}.decode"] = sha(engine.lower_decode())
        for bucket in engine.prefill_buckets:
            out[f"{model}.prefill{bucket}"] = sha(engine.lower_prefill(bucket))
    return out


if __name__ == "__main__":
    print(json.dumps(lowered_sha(), indent=1))
