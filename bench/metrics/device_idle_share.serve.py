"""Share of the traced serving window in which no program ran on the
device: 1 - (union of program intervals) / window."""


def read(run):
    if run.trace is None or run.kind != "serve":
        return None
    return 100.0 * run.trace.idle_share
