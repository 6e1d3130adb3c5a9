"""The blocks the sparse layers' decode reads visit over the blocks their
rows hold, by the program's own counts in its decode step records
(``blocks_read`` and ``blocks_held``, an entry per sparse layer: a key-value
head's table of selected pages against the row's pages), mean over the
window's decode steps. 100 is attention over everything held (every row under
the dense length); the selection reads at most ``topk`` blocks a row, so the
share falls as the rows grow long. It says whether the traffic still drives
the selection when a knee, a rate or the prompts' lengths move. Nothing to
read where the program keeps no such counts."""
from benchmark.decoderecords import decode_counts

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    reads = decode_counts(run, "blocks_read")
    helds = decode_counts(run, "blocks_held")
    if run["kind"] != "serve" or not reads or not helds:
        return None
    shares = [100.0 * sum(r) / sum(h) for r, h in zip(reads, helds) if sum(h)]
    return sum(shares) / len(shares) if shares else None
