"""95th percentile over requests of their mean time per output token
inside the window: (last delivery - first delivery) / (tokens - 1), for
requests with two tokens or more delivered in it.  Delivery is when the
engine's step returns, so stalls from other requests' prefills count."""


def read(run):
    if run.kind != "serve":
        return None
    from bench.lib.stats import percentile, window_tokens

    return percentile(((t[-1] - t[0]) / (len(t) - 1) * 1e3
                       for _, t in window_tokens(run) if len(t) > 1), 95)
