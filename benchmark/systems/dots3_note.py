"""dots3-note-prev serving through the program's normal path:
``models.dots3_note`` + ``inference.GenerationEngine(paged=True)`` (two page
groups: the full layers' pools keep every position, the window layers' the
last 513) + ``ContinuousBatcher``, the weights in the configuration's stated
dtype."""
from __future__ import annotations

from .bert import reference_key

_NAMES = [
    (r"word_embed_weight", "embed.word"),
    (r"head_weight", "head.w"),
    (r"norm_gamma", "norm.gamma"),
    (r"layer(\d+)_(attn_norm|ffn_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_mla_(q_norm|kv_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_mla_(q_a|q_b|kv_a|kv_b|o)_weight", r"layer\1.\2.w"),
    (r"layer(\d+)_mla_gate_weight", r"layer\1.attn_gate.w"),
    (r"layer(\d+)_mla_index_(q_b|k)_weight", r"layer\1.index.\2.w"),
    (r"layer(\d+)_mla_index_w_weight", r"layer\1.index.weights.w"),
    (r"layer(\d+)_mla_index_k_norm_(gamma|beta)", r"layer\1.index.k_norm.\2"),
    (r"layer(\d+)_ffn_(gate|up|down)_weight", r"layer\1.\2.w"),
    (r"layer(\d+)_moe_router_weight", r"layer\1.router.w"),
    (r"layer(\d+)_moe_router_bias", r"layer\1.router.bias"),
    (r"layer(\d+)_moe_experts_(gate|up|down)_weight", r"layer\1.experts.\2.w"),
    (r"layer(\d+)_moe_shared_(gate|up|down)_weight", r"layer\1.shared.\2.w"),
]


def model_sizes(config):
    """The model's sizes by the names ``models.dots3_note`` gives them."""
    c = config
    return dict(
        num_layers=c["n_layer"], units=c["hidden_size"],
        layer_types=tuple(c["layer_types"][:c["n_layer"]]),
        num_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        attention_gate=c["attention_gate_type"] == "headwise",
        swa_num_heads=c["swa_num_attention_heads"],
        swa_q_lora_rank=c["swa_q_lora_rank"],
        swa_kv_lora_rank=c["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=c["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=c["swa_qk_rope_head_dim"],
        swa_v_head_dim=c["swa_v_head_dim"],
        swa_rope_theta=float(c["swa_rope_theta"]),
        swa_attention_gate=c["swa_attention_gate_type"] == "headwise",
        sliding_window=c["sliding_window_size"],
        index_n_heads=c["index_n_heads"], index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"],
        lora_rescale=bool(c["apply_mla_qkv_lora_rescale"]),
        hidden_size=c["intermediate_size"],
        expert_hidden_size=c["moe_intermediate_size"],
        num_routed_experts=c["n_routed_experts"],
        num_shared_experts=c["n_shared_experts"],
        experts_per_token=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        first_k_dense=c["first_k_dense_replace"], vocab_size=c["n_vocab"],
        max_length=c["engine"].get("max_length", c["max_position_embeddings"]),
        rms_norm_eps=c["rms_norm_eps"])


def hand_over(net, weights, dtype):
    """Give every parameter of the uninitialised ``net`` the benchmark's
    weight, cast to ``dtype`` one leaf at a time (no third copy of the model
    is ever held), as loading a checkpoint does. The reference stores a
    weight (out, in); the program's stacked experts are (in, out), as its
    grouped product reads them. Returns {program name: reference key}."""
    import jax.numpy as jnp

    names = {}
    for name, p in net.collect_params().items():
        key = names[name] = reference_key(name, _NAMES)
        leaf = weights[key].astype(dtype)
        if ".experts." in key:
            leaf = jnp.swapaxes(leaf, 1, 2)
        p.grad_req = "null"  # served, never trained: no gradient buffers
        p.set_data(leaf)
    if set(names.values()) != set(weights):
        raise KeyError(f"weights never handed over: "
                       f"{sorted(set(weights) - set(names.values()))}")
    return names


def build_net(config, weights):
    from mxnet_tpu.models import dots3_note

    dtype = config["precision"]["weights"]
    net = dots3_note.get_dots3_note(
        "dots3_note", dtype=dtype, held_experts=config["held_experts"],
        **model_sizes(config))
    hand_over(net, weights, dtype)
    return net


def build_serve(config, weights):
    """(GenerationEngine, ContinuousBatcher) with the settings of the
    configuration's ``engine`` group; everything else is the program's
    default."""
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine

    engine = GenerationEngine(build_net(config, weights), **config["engine"])
    return engine, ContinuousBatcher(engine)
