"""DeepSeek-V2 serving through the program's normal path:
``models.deepseek_v2`` + ``inference.GenerationEngine(paged=True)`` +
``ContinuousBatcher``, the weights in the configuration's stated dtype."""
from __future__ import annotations

from .bert import reference_key

_NAMES = [
    (r"word_embed_weight", "embed.word"),
    (r"head_weight", "head.w"),
    (r"norm_gamma", "norm.gamma"),
    (r"layer(\d+)_(attn_norm|ffn_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_mla_(q_norm|kv_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_mla_(q_a|q_b|kv_a|kv_b|o)_weight", r"layer\1.\2.w"),
    (r"layer(\d+)_ffn_(gate|up|down)_weight", r"layer\1.\2.w"),
    (r"layer(\d+)_moe_router_weight", r"layer\1.router.w"),
    (r"layer(\d+)_moe_experts_(gate|up|down)_weight", r"layer\1.experts.\2.w"),
    (r"layer(\d+)_moe_shared_(gate|up|down)_weight", r"layer\1.shared.\2.w"),
]


def model_sizes(config):
    """The model's sizes by the names ``models.deepseek_v2`` gives them."""
    rs = config["rope_scaling"]
    return dict(
        num_layers=config["n_layer"], units=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], hidden_size=config["intermediate_size"],
        expert_hidden_size=config["moe_intermediate_size"],
        num_routed_experts=config["n_routed_experts"],
        num_shared_experts=config["n_shared_experts"],
        experts_per_token=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        first_k_dense=config["first_k_dense_replace"],
        vocab_size=config["n_vocab"],
        max_length=config["engine"].get("max_length",
                                        config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max_length=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]), rope_mscale=rs["mscale"],
        rope_mscale_all_dim=rs["mscale_all_dim"],
        rms_norm_eps=config["rms_norm_eps"])


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
    from mxnet_tpu.models import deepseek_v2

    dtype = config["precision"]["weights"]
    net = deepseek_v2.get_deepseek_v2(
        "deepseek_v2", dtype=dtype, held_experts=config["held_experts"],
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
