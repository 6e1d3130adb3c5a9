"""Olmo-Hybrid-7B serving through the program's normal path:
``models.olmo_hybrid`` + ``inference.GenerationEngine(paged=True)`` (the
full layers' key/value pools in the page group ``all``, the linear layers'
recurrent state by slot beside them) + ``ContinuousBatcher``, the matrices
in the configuration's stated dtype, the linear layers' gates, convolution
and state in float32."""
from __future__ import annotations

from .bert import reference_key

_NAMES = [
    (r"word_embed_weight", "embed.word"),
    (r"head_weight", "head.w"),
    (r"norm_gamma", "norm.gamma"),
    (r"layer(\d+)_(mixer_norm|ffn_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_ffn_(gate|up|down)_weight", r"layer\1.ffn.\2.w"),
    (r"layer(\d+)_attn_(q|k|v|o)_weight", r"layer\1.attn.\2.w"),
    (r"layer(\d+)_attn_(q_norm|k_norm)_gamma", r"layer\1.attn.\2.gamma"),
    (r"layer(\d+)_gdn_(q|k|v|g|o|a|b)_weight", r"layer\1.gdn.\2.w"),
    (r"layer(\d+)_gdn_conv_weight", r"layer\1.gdn.conv.w"),
    (r"layer(\d+)_gdn_(A_log|dt_bias)", r"layer\1.gdn.\2"),
    (r"layer(\d+)_gdn_o_norm_gamma", r"layer\1.gdn.o_norm.gamma"),
]


def model_sizes(config):
    """The model's sizes by the names ``models.olmo_hybrid`` gives them."""
    c, n = config, config["n_layer"]
    return dict(
        num_layers=n, units=c["hidden_size"],
        hidden_size=c["intermediate_size"],
        num_heads=c["num_attention_heads"], head_dim=c["head_dim"],
        layer_types=tuple(c["layer_types"][:n]),
        linear_heads=c["linear_num_value_heads"],
        linear_key_dim=c["linear_key_head_dim"],
        linear_value_dim=c["linear_value_head_dim"],
        conv_width=c["linear_conv_kernel_dim"], vocab_size=c["n_vocab"],
        max_length=c["engine"].get("max_length", c["max_position_embeddings"]),
        rms_norm_eps=c["rms_norm_eps"])


def hand_over(net, config, weights):
    """Give every parameter of the uninitialised ``net`` the benchmark's
    weight, cast to the parameter's own dtype one leaf at a time (no third
    copy of the model is ever held), as loading a checkpoint does. ``A_log``
    and ``dt_bias`` are handed over as the reference reads them: the drawn
    leaf moved by the configuration's ``decay_init`` means
    (``reference.olmo_hybrid.decay_leaf``). Returns {program name:
    reference key}."""
    from benchmark.reference.olmo_hybrid import decay_leaf

    names = {}
    for name, p in net.collect_params().items():
        key = names[name] = reference_key(name, _NAMES)
        leaf = decay_leaf(config, key, weights[key])
        p.grad_req = "null"  # served, never trained: no gradient buffers
        p.set_data(leaf.astype(p.dtype))
    if set(names.values()) != set(weights):
        raise KeyError(f"weights never handed over: "
                       f"{sorted(set(weights) - set(names.values()))}")
    return names


def build_net(config, weights):
    from mxnet_tpu.models import olmo_hybrid

    if config["num_attention_heads"] != config["num_key_value_heads"] \
            or config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("models.olmo_hybrid has equal query, key and value "
                         "head counts in both kinds of layer")
    net = olmo_hybrid.get_olmo_hybrid(
        "olmo_hybrid_7b", dtype=config["precision"]["weights"],
        **model_sizes(config))
    hand_over(net, config, weights)
    return net


def build_serve(config, weights):
    """(GenerationEngine, ContinuousBatcher) with the settings of the
    configuration's ``engine`` group; everything else is the program's
    default."""
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine

    engine = GenerationEngine(build_net(config, weights), **config["engine"])
    return engine, ContinuousBatcher(engine)
