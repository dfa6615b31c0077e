"""Median time a request waited from its due time to its admission into a
slot, as the engine's request records give it."""


def read(run):
    if run.kind != "serve":
        return None
    from bench.lib.stats import percentile

    return percentile(((r["admitted"] - r["due"]) * 1e3
                       for r in run.requests if r["admitted"] is not None),
                      50)
