"""SmallThinker-21BA3B-Instruct serving through the program's normal path:
``models.smallthinker`` + ``inference.GenerationEngine(paged=True)`` (two
page groups of ordinary key/value pools: the full layers' keep every
position, the window layers' the last 4,096) + ``ContinuousBatcher``, the
weights in the configuration's stated dtype."""
from __future__ import annotations

from .bert import reference_key

_NAMES = [
    (r"word_embed_weight", "embed.word"),
    (r"head_weight", "head.w"),
    (r"norm_gamma", "norm.gamma"),
    (r"layer(\d+)_(attn_norm|ffn_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_attn_(q|k|v|o)_weight", r"layer\1.\2.w"),
    (r"layer(\d+)_moe_router_weight", r"layer\1.router.w"),
    (r"layer(\d+)_moe_experts_(gate|up|down)_weight", r"layer\1.experts.\2.w"),
]


def model_sizes(config):
    """The model's sizes by the names ``models.smallthinker`` gives them."""
    c, n = config, config["n_layer"]
    return dict(
        num_layers=n, units=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        sliding_window=c["sliding_window_size"],
        window_layout=tuple(c["sliding_window_layout"][:n]),
        rope_layout=tuple(c["rope_layout"][:n]),
        rope_theta=float(c["rope_theta"]),
        expert_hidden_size=c["moe_ffn_hidden_size"],
        num_routed_experts=c["moe_num_primary_experts"],
        experts_per_token=c["moe_num_active_primary_experts"],
        norm_topk_prob=bool(c["norm_topk_prob"]), vocab_size=c["n_vocab"],
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
    from mxnet_tpu.models import smallthinker

    dtype = config["precision"]["weights"]
    net = smallthinker.get_smallthinker(
        "smallthinker_21b", dtype=dtype, held_experts=config["held_experts"],
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
