"""How ``sample_1chip.xplane.pb`` / ``sample_4chip.xplane.pb`` beside this
file were recorded (on the chip, by hand): a few steps of a small program
with a loop in it, and on several chips a reduction across them, under the
benchmark's own host spans. ``python3 -m benchmark.trace.record_sample``
writes ``chiprun_out/sample_<n>chip.xplane.pb`` and prints what the trace
holds. With the argument ``kernel`` (one chip) the program also runs a
Pallas kernel whose instruction is NOT named ``custom_call*``, and the file
is ``sample_kernel.xplane.pb``: what ``reduce.is_custom_call`` has to find
by the operation's text. The tests reduce the committed copies."""
from __future__ import annotations

import os
import shutil
import sys

from .. import harness, tracing
from . import reduce as tr


def main(kernel=False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = harness.require_devices(1)
    devices = jax.devices()
    mesh = Mesh(devices, ("d",))
    rows = NamedSharding(mesh, P("d"))

    def doubled(h):
        from jax.experimental import pallas as pl

        def probe(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        return pl.pallas_call(probe, name="probe_scale",
                              out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype))(h)

    @jax.jit
    def step(x, w):
        def body(_, h):
            return jnp.tanh(h @ w)
        h = jax.lax.fori_loop(0, 4, body, x)
        if kernel:
            h = doubled(h)
        return jnp.sum(h * h)  # over the sharded rows: an all-reduce

    x = jax.device_put(jnp.ones((256 * len(devices), 512), jnp.bfloat16), rows)
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16) * 0.01,
                       NamedSharding(mesh, P()))
    step(x, w).block_until_ready()
    result = {}
    root = harness.ROOT
    out = os.path.join(root, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    # as tracing.traced does, but the file is kept
    directory = os.path.join(root, ".benchmark_trace")
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    with tracing.span(tracing.WINDOW):
        for _ in range(3):
            with tracing.span("bench.step"):
                y = step(x, w)
            with tracing.span("bench.wait"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_trace(directory)
    keep = os.path.join(out, "sample_kernel.xplane.pb" if kernel
                        else f"sample_{len(devices)}chip.xplane.pb")
    shutil.copy(path, keep)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events),
                  [(e.name[:60], e.start_ns, e.duration_ns) for e in events[:6]])
            for e in events:  # a kernel's record in full, with its stats
                if "custom" in e.name or "probe" in e.name:
                    print("    KERNEL?", e.name, dict(e.stats))
                    break
    result.update(tr.reduce(tr.load(path, "tpu"), tracing.WINDOW))
    print(os.path.getsize(keep), "bytes;", result)


if __name__ == "__main__":
    sys.exit(main(kernel=sys.argv[1:] == ["kernel"]))
