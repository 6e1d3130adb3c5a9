"""BERT pretraining through the program's normal path:
``models.bert`` + ``parallel.TrainStep(amp=...)`` + ``optimizer.Adam``."""
from __future__ import annotations

import re

_NAMES = [  # program parameter name (after its block prefix) -> reference key
    (r"word_embed_weight", "embed.word"),
    (r"token_type_embed_weight", "embed.type"),
    (r"position_embed_weight", "embed.position"),
    (r"embed_ln_(gamma|beta)", r"embed.ln.\1"),
    (r"enc_layer(\d+)_attn_(qkv|proj)_weight", r"layer\1.\2.w"),
    (r"enc_layer(\d+)_attn_(qkv|proj)_bias", r"layer\1.\2.b"),
    (r"enc_layer(\d+)_(ffn1|ffn2)_weight", r"layer\1.\2.w"),
    (r"enc_layer(\d+)_(ffn1|ffn2)_bias", r"layer\1.\2.b"),
    (r"enc_layer(\d+)_(ln1|ln2)_(gamma|beta)", r"layer\1.\2.\3"),
    (r"pooler_weight", "pooler.w"), (r"pooler_bias", "pooler.b"),
    (r"mlmt_weight", "mlm.transform.w"), (r"mlmt_bias", "mlm.transform.b"),
    (r"mlmln_(gamma|beta)", r"mlm.ln.\1"),
    (r"mlmdec_weight", "mlm.decoder.w"), (r"mlmdec_bias", "mlm.decoder.b"),
    (r"nsp_weight", "nsp.w"), (r"nsp_bias", "nsp.b"),
]


def reference_key(name, table=_NAMES):
    tail = name.split("_", 1)[1]  # drop the block's own prefix
    for pattern, key in table:
        if re.fullmatch(pattern, tail):
            return re.sub(pattern, key, tail)
    raise KeyError(f"no reference key for the program's parameter {name!r}")


def hand_over(net, weights, table=_NAMES):
    """Give every parameter of ``net`` the benchmark's weight, as loading a
    checkpoint does. Returns {program name: reference key}."""
    names = {}
    for name, p in net.collect_params().items():
        names[name] = reference_key(name, table)
        p.set_data(weights[names[name]])
    if set(names.values()) != set(weights):
        raise KeyError(f"weights never handed over: "
                       f"{sorted(set(weights) - set(names.values()))}")
    return names


def build_train(config, mix, weights):
    """(TrainStep, {program name: reference key})."""
    from mxnet_tpu import optimizer
    from mxnet_tpu.models import bert
    from mxnet_tpu.parallel import Layout, TrainStep

    net = bert.get_bert(
        "bert_large", pretrain_head=True,
        dropout=config["dropout"]["hidden"],
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        max_length=config["max_position_embeddings"],
        vocab_size=config["vocab_size"],
        token_type_vocab=config["type_vocab_size"])
    names = hand_over(net, weights)

    def loss_fn(out, labels, label_weights, nsp_labels):
        return bert.pretrain_loss(*out, labels, label_weights, nsp_labels)

    opt = config["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"this adaptor builds Adam, not {opt['name']!r}")
    layout = Layout(**mix["layout"]) if mix.get("layout") else None
    ts = TrainStep(net, loss_fn,
                   optimizer.Adam(learning_rate=opt["learning_rate"],
                                  beta1=opt["beta1"], beta2=opt["beta2"],
                                  epsilon=opt["epsilon"]),
                   n_model_inputs=4, amp=config["precision"]["compute"],
                   layout=layout)
    return ts, names
