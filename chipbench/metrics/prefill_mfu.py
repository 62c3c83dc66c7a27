"""prefill.mfu: the FLOPs prefill needs (the family's ``prefill``: projections,
causal attention, the last position's lm_head) over the traced prefill
spans' time times the chips' bf16 peak, in %."""


def read(records):
    spans = (records.get("trace") or {}).get("spans", {}).get("prefill")
    if not spans:
        return None
    flops = sum(s["work"].total_flops for s in spans)  # per chip
    seconds = sum(s["seconds"] for s in spans)
    return 100.0 * flops / (seconds * records["peak"]["bf16_flop_per_s"])
