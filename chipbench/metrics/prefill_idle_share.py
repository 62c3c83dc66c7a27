"""prefill.idle_share: the share of the traced prefill spans in which no
operation ran on the device (averaged over the chips), in %."""


def read(records):
    spans = (records.get("trace") or {}).get("spans", {}).get("prefill")
    if not spans:
        return None
    busy = sum(s["busy_s"] for s in spans)
    return 100.0 * (1.0 - busy / sum(s["seconds"] for s in spans))
