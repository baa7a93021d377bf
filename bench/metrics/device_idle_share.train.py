"""Device idle share of the training window, in %: 1 minus the union of
device-operation intervals over the window, on the idlest of the cell's
devices (profiler trace)."""


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * max(d["idle_share"] for d in trace["devices"].values())
