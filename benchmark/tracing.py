"""One traced slice of a run: start the profiler, mark the window with a
host span, stop, reduce."""
from __future__ import annotations

import contextlib
import os
import shutil

from .trace import reduce as tr

WINDOW = "bench.window"


def span(name):
    """A host span in the profiler's own trace (``bench.*`` by convention)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(root, result):
    """Trace the body into ``<root>/.benchmark_trace`` (a fixed path inside
    the checkout, emptied first). The body marks its measured part with
    ``span(WINDOW)``; the profiler starts and stops outside it. The reduced
    numbers land in ``result`` (a dict) when the body has ended."""
    import jax

    directory = os.path.join(root, ".benchmark_trace")
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the host
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    platform = jax.devices()[0].platform
    result.update(tr.reduce(tr.load(tr.find_trace(directory), platform), WINDOW))
    shutil.rmtree(directory, ignore_errors=True)
