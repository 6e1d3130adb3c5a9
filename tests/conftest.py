"""Test harness: 8 virtual CPU devices (SURVEY §4 — multi-node-without-a-
cluster testing), mirroring the reference's N-local-process KVStore CI.

The tests run on the CPU whatever the host holds: the platform is pinned
through jax.config, so a run started without JAX_PLATFORMS=cpu on a chip
host still never claims the chip.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def natkey(item):
    """Natural-sort key over a (param_name, value) item: block-name
    counters are process-global, so two identically-built nets get
    different numeric prefixes — plain lexicographic sort flips order once
    a counter hits two digits ("dense10" < "dense9"), pairing weights
    against biases."""
    import re

    return [int(t) if t.isdigit() else t
            for t in re.split(r"(\d+)", item[0])]


def load_tool(name):
    """A script of ``tools/`` (no package) as a module of its own; the
    golden families' shared instance is ``load_tool("families").load()``."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"tools_{name}_for_tests",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pytest_configure(config):
    # chaos marker (resilience subsystem): tests that *arm* fault injection
    # themselves, as opposed to the `make chaos` pass which arms
    # MXNET_TPU_FAULTS globally and runs the ordinary tier-1 suite under it
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (resilience subsystem); "
        "`make chaos` runs the tier-1 suite with MXNET_TPU_FAULTS armed")


@pytest.fixture(autouse=True)
def _seed():
    """Reference: @with_seed() decorator — reproducible randomness per test."""
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield
    # amp.init() now genuinely changes op compute dtypes — never let that
    # global leak from one test into the next
    from mxnet_tpu.contrib import amp

    if amp.amp_dtype() is not None:
        amp._reset()


@pytest.fixture(autouse=True, scope="module")
def _fresh_step_records():
    """A module starts with empty step-record rings. The rings are the
    PROCESS's (``obs.step_records``) and a worker runs several modules one
    after another, in an order that follows their sizes: a check of "every
    ``prefill`` record of the process" (tests/benchmark/
    test_benchmark_minicpm_sala.py) must not meet the toys of the module
    that ran before it."""
    from mxnet_tpu import observability as obs

    for ring in obs._records.values():
        ring.clear()
    yield
