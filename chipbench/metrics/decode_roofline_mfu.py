"""decode.roofline_mfu: the least time the traced decode steps need (each
step the larger of its needed FLOPs over the bf16 peak and its needed
bytes over HBM bandwidth, per chip; ``counts.decode`` charges the weights
without the embedding table and the cache up to its filled length) over
the time the steps took, from dispatch until their tokens were on the
host, in %."""

from chipbench import counts


def read(records):
    spans = (records.get("trace") or {}).get("spans", {}).get("decode")
    if not spans:
        return None
    least = sum(counts.least_time(s["work"], records["peak"])[0]
                for s in spans)
    return 100.0 * least / sum(s["seconds"] for s in spans)
