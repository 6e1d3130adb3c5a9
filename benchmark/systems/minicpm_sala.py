"""MiniCPM-SALA serving through the program's normal path:
``models.minicpm_sala`` + ``inference.GenerationEngine(paged=True)`` (the
sparse layers' key, value and compressed-key pools in the page group
``all``, a page the size of a block; the lightning layers' decayed state by
slot beside them) + ``ContinuousBatcher``, the matrices in the
configuration's stated dtype, the state, the selector's scores and every
softmax in float32."""
from __future__ import annotations

from .bert import reference_key

_NAMES = [
    (r"word_embed_weight", "embed.word"),
    (r"head_weight", "head.w"),
    (r"norm_gamma", "norm.gamma"),
    (r"layer(\d+)_(mixer_norm|ffn_norm)_gamma", r"layer\1.\2.gamma"),
    (r"layer(\d+)_ffn_(gate|up|down)_weight", r"layer\1.ffn.\2.w"),
    (r"layer(\d+)_(attn|lin)_(q|k|v|g|o)_weight", r"layer\1.\2.\3.w"),
    (r"layer(\d+)_(attn|lin)_(q_norm|k_norm|o_norm)_gamma",
     r"layer\1.\2.\3.gamma"),
]


def model_sizes(config):
    """The model's sizes by the names ``models.minicpm_sala`` gives them."""
    c, n, sparse = config, config["n_layer"], config["sparse_config"]
    if c["lightning_nh"] != c["lightning_nkv"]:
        raise ValueError("models.minicpm_sala has equal query and key-value "
                         "head counts in a lightning layer")
    return dict(
        num_layers=n, published_layers=c["num_hidden_layers"],
        units=c["hidden_size"], hidden_size=c["intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mixer_types=tuple(c["mixer_types"][:n]),
        lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"], vocab_size=c["n_vocab"],
        max_length=c["engine"].get("max_length", c["max_position_embeddings"]),
        rms_norm_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
        scale_emb=float(c["scale_emb"]), scale_depth=float(c["scale_depth"]),
        dim_model_base=c["dim_model_base"],
        **{k: sparse[k] for k in ("kernel_size", "kernel_stride", "block_size",
                                  "topk", "init_blocks", "window_size",
                                  "dense_len")})


def hand_over(net, weights):
    """Give every parameter of the uninitialised ``net`` the benchmark's
    weight, cast to the parameter's own dtype one leaf at a time (no third
    copy of the model is ever held), as loading a checkpoint does. Returns
    {program name: reference key}."""
    names = {}
    for name, p in net.collect_params().items():
        key = names[name] = reference_key(name, _NAMES)
        p.grad_req = "null"  # served, never trained: no gradient buffers
        p.set_data(weights[key].astype(p.dtype))
    if set(names.values()) != set(weights):
        raise KeyError(f"weights never handed over: "
                       f"{sorted(set(weights) - set(names.values()))}")
    return names


def build_net(config, weights):
    from mxnet_tpu.models import minicpm_sala

    net = minicpm_sala.get_minicpm_sala(
        "minicpm_sala", dtype=config["precision"]["weights"],
        **model_sizes(config))
    hand_over(net, weights)
    return net


def build_serve(config, weights):
    """(GenerationEngine, ContinuousBatcher) with the settings of the
    configuration's ``engine`` group; everything else is the program's
    default."""
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine

    engine = GenerationEngine(build_net(config, weights), **config["engine"])
    return engine, ContinuousBatcher(engine)
