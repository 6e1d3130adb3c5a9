"""GPT-2 serving through the program's normal path: ``models.gpt2`` +
``inference.GenerationEngine(paged=True)`` + ``ContinuousBatcher``."""
from __future__ import annotations

from .bert import hand_over

_NAMES = [
    (r"word_embed_weight", "embed.word"),
    (r"position_embed_weight", "embed.position"),
    (r"layer(\d+)_(qkv|proj|ffn1|ffn2)_weight", r"layer\1.\2.w"),
    (r"layer(\d+)_(qkv|proj|ffn1|ffn2)_bias", r"layer\1.\2.b"),
    (r"layer(\d+)_(ln1|ln2)_(gamma|beta)", r"layer\1.\2.\3"),
    (r"lnf_(gamma|beta)", r"lnf.\1"),
]


def build_serve(config, weights):
    """(GenerationEngine, ContinuousBatcher) with the settings of the
    configuration's ``engine`` group; everything else is the program's
    default."""
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine
    from mxnet_tpu.models import gpt2

    net = gpt2.get_gpt2("gpt2_345m", dropout=0.0,
                        num_layers=config["n_layer"], units=config["n_embd"],
                        num_heads=config["n_head"],
                        max_length=config["n_ctx"],
                        vocab_size=config["n_vocab"])
    hand_over(net, weights, _NAMES)
    engine = GenerationEngine(net, **config["engine"])
    return engine, ContinuousBatcher(engine)
