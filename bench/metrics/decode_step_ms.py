"""Device time of the paged decode-chunk program (``jit_chunk``) in the
traced window, per decode step it ran."""

PROGRAM = "jit_chunk"


def read(run):
    tr = run.trace
    if tr is None or not tr.program_calls.get(PROGRAM):
        return None
    steps = tr.program_calls[PROGRAM] * run.workload["engine"]["decode_chunk"]
    return tr.program_s[PROGRAM] / steps * 1e3
