"""The decode step's share of its roofline, in percent: the least time
the chip could take for the traced decode steps over the device time they
took.  The least time is the larger of their bytes over the peak
bandwidth and their operations over the peak rate.  Bytes: the bf16
weights once per step, the KV cache of each slot at its real length, and
one token's KV written per slot.  Operations: the projections and head
for one token per slot, and attention over each slot's length."""

MODULE = "jit_decode_step"


def read(run):
    d = run["trace"].module_durations(MODULE) if run.get("trace") else []
    w = run.get("traced") or {}
    if not d or not w.get("decode_steps"):
        return None
    s, c, pk = run["cfg"]["sizes"], run["counts"], run["peak"]
    kv = c.kv_bytes_per_token(s)
    nbytes = (w["decode_steps"] * c.weight_bytes(s)
              + kv * (w["decode_attended"] + w["decode_tokens"]))
    flops = c.forward_flops(s, w["decode_tokens"], w["decode_attended"])
    least = max(nbytes / pk["hbm_bytes_per_s"],
                flops / pk["bf16_flops_per_s"])
    return 100.0 * (least / w["decode_steps"]) / (sum(d) / len(d))
