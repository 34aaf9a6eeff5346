"""The B=1 prefill's share of its roofline for a hybrid of state-space
and attention layers, in percent: the least time the chip could take for
an average prefill of the traced window over the average device time of
the traced prefill calls.  The least time is the larger of bytes over
the peak bandwidth and operations over the peak rate.  Per prefill: the
bf16 weights once, the prompt's KV and the sequence's SSM and conv state
written.  Operations: ``forward_flops`` of the prompt tokens (causal
attention over them), the head once per prefill."""

MODULE = "jit_prefill_step"


def read(run):
    d = run["trace"].module_durations(MODULE) if run.get("trace") else []
    w = run.get("traced") or {}
    c = run.get("counts")
    if not d or not w.get("prefills") \
            or not hasattr(c, "state_bytes_per_seq"):
        return None
    s, pk = run["cfg"]["sizes"], run["peak"]
    n = w["prefills"]
    nbytes = (n * (c.weight_bytes(s) + c.state_bytes_per_seq(s))
              + c.kv_bytes_per_token(s) * w["prompt_tokens"])
    flops = c.forward_flops(s, w["prompt_tokens"], w["prefill_attended"],
                            heads=n)
    least = max(nbytes / pk["hbm_bytes_per_s"],
                flops / pk["bf16_flops_per_s"])
    return 100.0 * (least / n) / (sum(d) / len(d))
