"""Model FLOP utilization of serving, in percent: the operations the
model needs for the prompt and output tokens processed in the traced part
of the window (projections, attention over each token's context, and the
head only where a token is emitted), over the traced seconds, over the
chips' peak bf16 rate."""


def read(run):
    tr, w = run.get("trace"), run.get("traced") or {}
    if tr is None or not (w.get("prompt_tokens") or w.get("decode_tokens")):
        return None
    s, c, pk = run["cfg"]["sizes"], run["counts"], run["peak"]
    flops = (c.forward_flops(s, w["prompt_tokens"], w["prefill_attended"],
                             heads=w["prefills"])
             + c.forward_flops(s, w["decode_tokens"], w["decode_attended"]))
    return 100.0 * flops / tr.window_s / (run["chips"]
                                         * pk["bf16_flops_per_s"])
