"""Model FLOP utilization of training, in percent: the forward and
backward operations of the steps completed in the traced part of the
window, with no recomputation counted, over the traced seconds, over the
chips' peak bf16 rate."""


def read(run):
    tr, w = run.get("trace"), run.get("traced") or {}
    if tr is None or not w.get("steps"):
        return None
    s, c, pk = run["cfg"]["sizes"], run["counts"], run["peak"]
    flops = w["steps"] * c.train_flops(s, w["batch"], w["seq"])
    return 100.0 * flops / tr.window_s / (run["chips"]
                                         * pk["bf16_flops_per_s"])
