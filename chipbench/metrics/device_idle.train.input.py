"""Share of the traced window in which the device ran no operation
while the trainer built and placed a step's batch (host spans
``train.input``), in percent, averaged over the cell's devices."""

from chipbench import spans

SPANS = ("train.input",)


def read(run):
    return spans.idle_share_in_spans(run.get("trace"), SPANS)
