"""Share of the traced window in which the device ran no operation
while the server copied an admitted request's KV cache and recurrent
state into its slot (host spans ``serve.insert``, inside
``serve.prefill``), in percent, averaged over the cell's devices."""

from chipbench import spans

SPANS = ("serve.insert",)


def read(run):
    return spans.idle_share_in_spans(run.get("trace"), SPANS)
