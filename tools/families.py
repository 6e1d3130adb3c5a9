#!/usr/bin/env python
"""The golden program families — ONE definition shared by every gate.

``make shardcheck`` (sharding + comm) and ``make memcheck`` (buffer
liveness) audit the same ten representative programs; this module owns
their constructors so a family change can never drift between gates
(ISSUE 13). Builders are
memoized where two families audit the SAME object (the two fsdp families
share one TrainStep — step vs window program — and the serving families
share engines), so one model build/compile serves each pair per run.

Import via ``importlib`` from the gate scripts (tools/ is not a package):

    fams = load().FAMILIES          # name -> () -> ProgramAudit
"""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import os
import sys
import unittest.mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

#: gate-facing family order (memcheck's default ordering)
FAMILY_NAMES = ("step_dp8", "step_fsdp", "window_fsdp", "step_pp",
                "step_moe_fsdp", "prefill", "decode", "decode_paged",
                "verify_spec", "decode_prefix")


def load():
    """Load THIS module through importlib under a stable name, so every
    gate (and test) shares one module instance — and therefore one
    memoized model build per family pair — per process."""
    name = "mxnet_tpu_golden_families"
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            name, os.path.abspath(__file__))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


# -- program families --------------------------------------------------------
def _mlp_step(mesh, rules=None):
    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    ts = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                   optimizer.Adam(learning_rate=1e-3), mesh=mesh,
                   rules=rules)
    return ts, (x, nd.zeros((8, 8)))


def family_step_dp8():
    """Pure data parallelism: the gradient all-reduce pattern."""
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    ts, batch = _mlp_step(make_mesh(MeshConfig(dp=8)))
    return ts.audit(*batch)


@functools.lru_cache(maxsize=None)
def _fsdp_step():
    from mxnet_tpu.parallel import MeshConfig, ShardingRules, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    rules = ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1)
    return _mlp_step(mesh, rules)


def family_step_fsdp():
    """ZeRO dp=2 x fsdp=4: compute gathers + sharded-grad reductions."""
    ts, batch = _fsdp_step()
    return ts.audit(*batch)


def family_window_fsdp():
    """The fused 2-step scan window over the same ZeRO layout."""
    ts, batch = _fsdp_step()
    return ts.audit(*batch, window=2)


@functools.lru_cache(maxsize=None)
def _pp_step():
    """GPipe pipeline over pp=8, declared through ONE Layout."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.parallel import Layout, TrainStep
    from mxnet_tpu.parallel.blocks import PipelineStages

    mx.random.seed(0)
    net = PipelineStages(8, 16)
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    layout = Layout(pp=8, rules=[
        (r"stages_weight$", ("pp", None, None)),
        (r"stages_bias$", ("pp", None)),
    ])
    ts = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                   optimizer.Adam(learning_rate=1e-3), layout=layout)
    return ts, (x, nd.zeros((8, 16)))


def family_step_pp():
    """Pipeline parallelism: stage ring ppermutes inside the GPipe scan."""
    ts, batch = _pp_step()
    return ts.audit(*batch)


@functools.lru_cache(maxsize=None)
def _moe_step():
    """Expert-parallel MoE composed with ZeRO storage: ep=4 x fsdp=2,
    expert weights stored ('ep','fsdp',None) and fsdp-gathered for
    compute, tokens riding the ep axis (the fused dp==ep layout)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.parallel import Layout, TrainStep
    from mxnet_tpu.parallel.blocks import MoEFFN

    mx.random.seed(0)
    net = MoEFFN(16, 32, 8)
    net.initialize()
    x = nd.ones((8, 4, 16))
    _ = net(x)
    layout = Layout(ep=4, fsdp=2,
                    rules=[(r"expert_w[12]$", ("ep", "fsdp", None))],
                    fsdp_axis="fsdp", min_fsdp_size=1, batch_axes=("ep",))
    ts = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                   optimizer.Adam(learning_rate=1e-3), layout=layout)
    return ts, (x, nd.zeros((8, 4, 16)))


def family_step_moe_fsdp():
    """MoE all_to_all dispatch/return composed with fsdp gathers."""
    ts, batch = _moe_step()
    return ts.audit(*batch)


def _tiny_gpt2(units):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import gpt2

    mx.random.seed(0)
    net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=2, units=units,
                        num_heads=2, max_length=64, vocab_size=64)
    net.initialize()
    _ = net(nd.array(np.zeros((1, 4), np.int32)))
    return net


@functools.lru_cache(maxsize=None)
def _engine():
    from mxnet_tpu.inference import GenerationEngine

    return GenerationEngine(_tiny_gpt2(32), batch_size=2, max_length=64,
                            prefill_buckets=(8, 16))


def family_decode():
    """The serving decode step: zero collectives is the contract."""
    return _engine().audit()


def family_prefill():
    """The bucket-8 prefill program (same zero-collective contract)."""
    return _engine().audit(bucket=8)


# -- the paged families, built as the chip runs them -------------------------
#: two heads of 64 fill one 128-lane tile: the narrowest model whose paged
#: reads pass ``pallas_paged_attention.paged_attention_refusal``
KERNEL_UNITS = 128


@contextlib.contextmanager
def kernel_traced():
    """Programs traced inside hold the paged attention kernel
    (interpreted) where the chip's would: the gate's backend check is told
    it stands on a TPU. The check is read at trace time, so an engine must
    be BUILT and its programs lowered inside (an engine's read path and
    its cached traces do not change afterwards)."""
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    with unittest.mock.patch.object(ppa, "_on_tpu", return_value=True):
        yield


def paged_engine(units=KERNEL_UNITS, net=None, **kw):
    """A paged engine over the tiny GPT-2 (two heads of ``units // 2``)."""
    from mxnet_tpu.inference import GenerationEngine

    return GenerationEngine(net or _tiny_gpt2(units), batch_size=2,
                            max_length=64, prefill_buckets=(8, 16),
                            paged=True, page_size=16, **kw)


@functools.lru_cache(maxsize=None)
@kernel_traced()
def _paged_engines():
    """One paged + one speculative engine over one net."""
    net = _tiny_gpt2(KERNEL_UNITS)
    return (paged_engine(net=net),
            paged_engine(net=net, draft_net=net, speculate_k=4))


@kernel_traced()
def family_decode_paged():
    """The paged decode step: page-table carry + pools, zero collectives."""
    return _paged_engines()[0].audit()


@kernel_traced()
def family_verify_spec():
    """The speculative verify pass (k+1 positions, one program)."""
    return _paged_engines()[1].audit(program="verify")


@functools.lru_cache(maxsize=None)
@kernel_traced()
def _prefix_engine():
    """A prefix-cache paged engine, audited on the copy-on-write
    page-copy program (prefix sharing, ISSUE 19)."""
    return paged_engine(prefix_cache=True)


@kernel_traced()
def family_decode_prefix():
    """The CoW page-copy program behind prefix sharing: carry-only
    inputs, 100% donation, zero collectives — same serving contract."""
    return _prefix_engine().audit(program="cow")


FAMILIES = {
    "step_dp8": family_step_dp8,
    "step_fsdp": family_step_fsdp,
    "window_fsdp": family_window_fsdp,
    "step_pp": family_step_pp,
    "step_moe_fsdp": family_step_moe_fsdp,
    "decode": family_decode,
    "prefill": family_prefill,
    "decode_paged": family_decode_paged,
    "verify_spec": family_verify_spec,
    "decode_prefix": family_decode_prefix,
}
