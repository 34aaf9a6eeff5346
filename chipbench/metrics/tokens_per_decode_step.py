"""Output tokens per batched decode step over the whole window, from the
server's own counters (``tokens_out`` and ``decode_steps``); over the
slot count it is the decode batch's occupancy."""


def read(run):
    c = run.get("counters")
    if not c or not c["decode_steps"]:
        return None
    return c["tokens_out"] / c["decode_steps"]
