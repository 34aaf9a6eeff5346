"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the cell's devices."""


def read(run):
    tr = run.get("trace")
    idle = tr.idle_share() if tr is not None else None
    return None if idle is None else 100.0 * idle
