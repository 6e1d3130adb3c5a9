"""The expert layers' route through the engine (PR 41): a tiny
dots3-note-prev and a tiny DeepSeek-V2 that hold 2 of their 16 experts, so
that ``held_expert_ffn`` builds its branch over the sorted pairs (a prefix
while a call's held pairs fit it, every pair when they do not), serve
through ``ContinuousBatcher`` the tokens of the whole-length program; the
model's count ``moe_whole_path`` comes back with a decode step's tokens and,
behind the first token, in a prefill's ONE blocking read; the engine sums it
into ``moe_route_total{path=prefix|whole}``."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu import observability as obs
from mxnet_tpu.parallel import moe

from benchmark.weights import make_weights
from test_serve_step_record import PREFILL_PHASES  # as PR 38 pinned them

TOP_K, HELD, EXPERTS, ROWS = 3, [1, 9], 16, 12
LENGTHS = [(13, 6), (30, 9), (7, 4), (5, 12), (16, 3)]


def toy(model):
    """(config, weights, adaptor, expert layers) of the tiny model with two
    experts held and twelve rows a decode step: 36 pairs a step, of which a
    prefix of 32 (4 x 36 x 2 / 16 = 18 in whole row tiles) is under."""
    if model == "dots3_note":
        import test_dots3_note as tiny
        from benchmark.reference import dots3_note as ref
        from benchmark.systems import dots3_note as adaptor
        pages = {"all": 200, "window": 60}
    else:
        import test_deepseek_v2 as tiny
        from benchmark.reference import deepseek_v2 as ref
        from benchmark.systems import deepseek_v2 as adaptor
        pages = 100
    cfg = tiny.tiny_config(held_experts=HELD)
    cfg["engine"] = dict(cfg["engine"], batch_size=ROWS, num_pages=pages,
                         prefill_buckets=[8, 16, 32])
    layers = cfg["n_layer"] - cfg["first_k_dense_replace"]
    return cfg, make_weights(ref.param_specs(cfg), tiny.SEED), adaptor, layers


def serve(cfg, weights, adaptor):
    """The requests of ``LENGTHS`` to their end: (outputs, the prefill
    records, the decode records, what ``moe_route_total`` gained)."""
    engine, batcher = adaptor.build_serve(cfg, weights)
    rng = np.random.default_rng(5)
    route = obs.counter("moe_route_total")
    before = {loop: len(obs.step_records(loop))
              for loop in ("prefill", "decode_step")}
    was = {path: route.value(path=path) for path in ("prefix", "whole")}
    reqs = [batcher.submit(rng.integers(1, cfg["n_vocab"], n).tolist(),
                           max_new_tokens=m) for n, m in LENGTHS]
    while batcher.pending or batcher.active:
        batcher.step()
    assert all(r.finish_reason == "length" for r in reqs)
    records = {loop: obs.step_records(loop)[n:] for loop, n in before.items()}
    gained = {path: route.value(path=path) - was[path] for path in was}
    return ([list(r.output) for r in reqs], records["prefill"],
            records["decode_step"], gained)


@pytest.mark.parametrize("model", ["dots3_note", "deepseek_v2"])
def test_the_route_comes_back_with_the_tokens_and_adds_up_to_the_calls(
        model, monkeypatch):
    cfg, weights, adaptor, layers = toy(model)
    outputs, prefills, decodes, gained = serve(cfg, weights, adaptor)
    # which programs hold the branch: by the rule, from their static shapes
    built = {pairs: moe.held_prefix_rows(pairs, len(HELD), EXPERTS) is not None
             for pairs in (ROWS * TOP_K, 8 * TOP_K, 16 * TOP_K, 32 * TOP_K)}
    assert built == {36: True, 24: False, 48: True, 96: True}
    assert len(prefills) == len(LENGTHS)
    calls = whole = 0
    for p in prefills:
        # one blocking read, the token's: the phases PR 38 pinned, and the
        # count a host integer beside the engine's own
        assert [m for m, _ in p.marks] == PREFILL_PHASES
        assert set(p.counts) == {"bucket", "suffix", "prompt", "pages",
                                 "adopted", "moe_whole_path"}
        n = p.counts["moe_whole_path"]
        assert isinstance(n, int) and 0 <= n <= layers
        if not built[p.counts["bucket"] * TOP_K]:  # no branch: always whole
            assert n == layers
        calls, whole = calls + layers, whole + n
    for d in decodes:
        route = np.asarray(d.counts["moe_whole_path"])
        assert route.shape == (layers, 1) and set(route.ravel()) <= {0, 1}
        # a call whose held pairs pass the prefix of 32 walks them whole
        assert (route[:, 0] == (np.asarray(d.counts["moe_pairs_held"]) > 32)
                ).all()
        calls, whole = calls + layers, whole + int(route.sum())
    assert gained == {"whole": whole, "prefix": calls - whole}
    assert 0 < gained["prefix"] and 0 < gained["whole"] < calls

    # the same requests through the whole-length programs (no branch built)
    monkeypatch.setattr(moe, "held_prefix_rows", lambda *a: None)
    plain, _, _, all_whole = serve(cfg, weights, adaptor)
    assert plain == outputs
    assert all_whole == {"whole": calls, "prefix": 0}
