"""Share of the traced window in which the device ran no operation
while the server loop filled slots, dispatched a decode step or handed
out its tokens (host spans ``serve.fill``, ``serve.dispatch``,
``serve.emit``), in percent, averaged over the cell's devices."""

from chipbench import spans

SPANS = ("serve.fill", "serve.dispatch", "serve.emit")


def read(run):
    return spans.idle_share_in_spans(run.get("trace"), SPANS)
