"""Output tokens delivered inside the window, first tokens included, over
the window's length (host clock)."""


def read(run):
    if run.kind != "serve":
        return None
    from bench.lib.stats import window_tokens

    n = sum(len(times) for _, times in window_tokens(run))
    return n / (run.window[1] - run.window[0])
