"""decode.collective_ms: device time per decode step inside collective
operations (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all), averaged over the chips, in ms."""


def read(records):
    spans = (records.get("trace") or {}).get("spans", {}).get("decode")
    if not spans:
        return None
    return 1e3 * sum(s["collective_s"] for s in spans) / len(spans)
