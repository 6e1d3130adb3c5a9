"""The no-fallback contracts of the chip bring-up (ISSUE 21), in-process and
cheap: the smoke's device check, where the compile cache goes, and interpret
mode refused on a TPU backend. The smoke itself runs through the chip tool."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from mxnet_tpu import config
from mxnet_tpu.ops import pallas_common


def test_smoke_refuses_cpu_before_building_anything(monkeypatch):
    def built(*a, **k):
        raise AssertionError("a phase ran without a TPU")

    for phase in ("phase_train", "phase_train_four_chips", "phase_serve",
                  "phase_serve_latent", "phase_kernels"):
        monkeypatch.setattr(chip_smoke, phase, built)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(RuntimeError, match=r"needs a TPU.*'cpu'"):
        chip_smoke.main()


def test_smoke_last_line_is_the_drivers_contract(monkeypatch, capsys):
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda n: ([object()], device, {"jax": "x"}))
    for phase in ("phase_train", "phase_serve", "phase_serve_latent",
                  "phase_kernels"):
        monkeypatch.setattr(chip_smoke, phase, lambda *a: {"found": 1})
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    chip_smoke.main()
    lines = capsys.readouterr().out.splitlines()
    # exactly these keys: the driver refuses a line that carries any other
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert all(p in lines[-2] for p in ("summary", "train", "serve",
                                        "latent", "kernels"))


def test_compile_cache_placement(monkeypatch, tmp_path):
    inside = config.COMPILE_CACHE_DIR
    assert inside.startswith(str(chip_smoke.__file__).rsplit("/", 1)[0])
    # what the package import did for this session: nothing where the
    # environment places the cache, else the fixed directory in the checkout
    before = jax.config.jax_compilation_cache_dir
    assert before == (os.environ.get("JAX_COMPILATION_CACHE_DIR") or inside)
    try:
        # where the environment places it (jax reads the variable itself at
        # start-up, simulated here), the package sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert config.apply_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert config.apply_compile_cache() == inside
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert "compile_cache" not in config.knobs()


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    from mxnet_tpu.ops.flash_attention import flash_attention
    from mxnet_tpu.ops.pallas_layernorm import layer_norm_fused
    from mxnet_tpu.ops.pallas_optimizer import adam_update_fused
    from mxnet_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_latent_attention_read)
    from mxnet_tpu.ops.pallas_softmax_xent import softmax_cross_entropy_fused

    x = jnp.zeros((8, 128), jnp.float32)
    q = jnp.zeros((1, 1, 128, 64), jnp.float32)
    pool = jnp.zeros((3, 8, 64), jnp.float32)
    calls = [
        lambda: flash_attention(q, q, q, interpret=True),
        lambda: layer_norm_fused(x, x[0], x[0], interpret=True),
        lambda: softmax_cross_entropy_fused(
            x, jnp.zeros((8,), jnp.int32), interpret=True),
        lambda: adam_update_fused(x, x, x, x, 1e-3, beta1=0.9, beta2=0.999,
                                  epsilon=1e-8, wd=0.0, interpret=True),
        lambda: paged_attention(
            q[:, :, :1, :], q[:, :, :1, :], q[:, :, :1, :], pool, pool,
            jnp.ones((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            interpret=True),
        lambda: paged_latent_attention_read(
            q[:, :1, :4, :32], q[:, :1, :4, :8], jnp.zeros((3, 8, 128)),
            jnp.ones((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), 1.0,
            interpret=True),
    ]
    assert pallas_common.resolve_interpret(None) is True  # the CPU tests
    monkeypatch.setattr(pallas_common, "on_tpu", lambda: True)
    assert pallas_common.resolve_interpret(None) is False
    for call in calls:
        with pytest.raises(RuntimeError, match="interpret mode"):
            call()


def test_launcher_refuses_workers_that_would_share_chips():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "launch", os.path.join(os.path.dirname(chip_smoke.__file__),
                               "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)
    chips = ["/dev/accel0", "/dev/accel1"]
    assert "ONE process" in launch.shared_chip_refusal(4, {}, chips)
    assert "ONE process" in launch.shared_chip_refusal(
        2, {"JAX_PLATFORMS": "tpu,cpu"}, chips)
    for n, env, found in ((1, {}, chips), (4, {}, []),
                          (4, {"JAX_PLATFORMS": "cpu"}, chips),
                          (4, {"TPU_VISIBLE_CHIPS": "0"}, chips)):
        assert launch.shared_chip_refusal(n, env, found) is None
