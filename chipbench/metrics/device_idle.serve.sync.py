"""Share of the traced window in which the device ran no operation
while the server loop waited for a decode step's tokens (host spans
``serve.sync``), in percent, averaged over the cell's devices."""

from chipbench import spans

SPANS = ("serve.sync",)


def read(run):
    return spans.idle_share_in_spans(run.get("trace"), SPANS)
