"""The decode step's share of its roofline for a hybrid of state-space
and attention layers, in percent: the least time the chip could take for
the traced decode steps over the device time they took.  The least time
is the larger of their bytes over the peak bandwidth and their
operations over the peak rate.  Bytes: the bf16 weights once per step,
each slot's KV at its real length and one token's KV written, and each
slot's SSM and conv state read and written once per state-space layer.
Operations: ``forward_flops`` of one token per slot, attention over each
slot's length."""

MODULE = "jit_decode_step"


def read(run):
    d = run["trace"].module_durations(MODULE) if run.get("trace") else []
    w = run.get("traced") or {}
    c = run.get("counts")
    if not d or not w.get("decode_steps") \
            or not hasattr(c, "state_bytes_per_seq"):
        return None
    s, pk = run["cfg"]["sizes"], run["peak"]
    nbytes = (w["decode_steps"] * c.weight_bytes(s)
              + c.kv_bytes_per_token(s) * (w["decode_attended"]
                                           + w["decode_tokens"])
              + 2 * c.state_bytes_per_seq(s) * w["decode_tokens"])
    flops = c.forward_flops(s, w["decode_tokens"], w["decode_attended"])
    least = max(nbytes / pk["hbm_bytes_per_s"],
                flops / pk["bf16_flops_per_s"])
    return 100.0 * (least / w["decode_steps"]) / (sum(d) / len(d))
