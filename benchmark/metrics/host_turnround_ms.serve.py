"""What the device waits for the host behind a decode step that was not
dispatched ahead, measured where it happens: over successive ``decode_step``
records A, B of the window with no ``prefill`` record between them, A
having dispatched nothing ahead and B dispatched by its own call (it has the
mark ``mx.gen.decode.dispatch``), the start of B's ``.dispatch`` phase (its
``mx.gen.decode.pages`` mark) minus A's ``mx.gen.decode.read`` mark: A's
tokens are on the host, B's arguments are not yet on their way. The mean, in
ms: the tokens' loop, the batcher's books, what the caller does between two
steps, the sweep, admission's look at the queue and the allocator's growth
(``decode_pages_ms.serve`` reads that last part alone).
``decode_step_mean_ms`` less the decode program's device time was this
number AND B's ``.dispatch`` phase AND the tail of its ``.read``, by
subtraction.

A cell above its knee has no such pair: its queue is never empty, so a step
not dispatched ahead stands behind an admission (``smallthinker_21b_serve_mixed``:
none in 3,177 steps; PERF.md, Findings, PR 38), and the reader returns None
there. What such a step waits for is its own ``.pages`` phase and the
prefill before it, which ``decode_pages_ms.serve`` and
``prefill_host_ms.serve`` read."""
import bisect

from benchmark.serverecords import DECODE, mark_ns, mean_ms, window_records

LAYER, UNIT, MOVES = "engine", "ms", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no such records, or marks no
    ``mx.gen.decode.pages``, or the window holds no such pair."""
    records = window_records(run, "decode_step")
    starts = [p.t0_ns for p in window_records(run, "prefill")]
    waits = []
    for a, b in zip(records, records[1:]):
        read_a = mark_ns(a, DECODE + ".read")
        pages_b = mark_ns(b, DECODE + ".pages")
        if (read_a is None or pages_b is None
                or mark_ns(a, DECODE + ".ahead") is not None
                or mark_ns(b, DECODE + ".dispatch") is None
                or bisect.bisect_left(starts, a.t0_ns)  # a prefill between
                != bisect.bisect_left(starts, b.t0_ns)):
            continue
        waits.append(pages_b - read_a)
    return mean_ms(waits)
