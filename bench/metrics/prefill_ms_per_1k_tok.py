"""Device time of the admission prefill programs (``jit_run``) in the
traced window, per thousand prompt tokens they prefilled."""

PROGRAM = "jit_run"


def read(run):
    tr = run.trace
    if tr is None or not tr.program_calls.get(PROGRAM):
        return None
    from bench.lib.stats import in_window

    tokens = sum(r["prompt_len"] for r in run.requests
                 if r["delivered"] and in_window(run, r["delivered"][0]))
    if not tokens:
        return None
    return tr.program_s[PROGRAM] / tokens * 1e6
