"""The positions the full layers' softmaxes read over the positions their
rows hold, by the program's own counts in its decode step records
(``dsa_read`` and ``dsa_held``, an entry per full layer), mean over the
window's decode steps. 100 is attention over everything held; the learned
selection reads at most ``index_topk`` positions a row, so the share falls
as the rows grow long."""
from benchmark.decoderecords import decode_counts

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    reads = decode_counts(run, "dsa_read")
    helds = decode_counts(run, "dsa_held")
    if run["kind"] != "serve" or not reads or not helds:
        return None
    shares = [100.0 * sum(r) / sum(h) for r, h in zip(reads, helds) if sum(h)]
    return sum(shares) / len(shares) if shares else None
