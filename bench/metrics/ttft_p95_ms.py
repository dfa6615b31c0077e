"""95th percentile, over every request due inside the window, of the time
from its due time to its first token (host clock).  A request that never
delivered a token is counted in ``failed`` instead."""


def read(run):
    if run.kind != "serve":
        return None
    from bench.lib.stats import percentile

    return percentile(((r["delivered"][0] - r["due"]) * 1e3
                       for r in run.requests if r["delivered"]), 95)
