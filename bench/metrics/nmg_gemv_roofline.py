"""Share of its roofline that the n:m:g kernel reaches inside the paged
decode chunk (``jit_chunk``): the least time of the work the decode steps
require of the configuration's n:m:g weights at the engine's slot width,
over the device time of the kernel's events in that program."""

PROGRAM = "jit_chunk"


def read(run):
    tr = run.trace
    if tr is None or run.kind != "serve" or run.peaks is None:
        return None
    from bench.lib import work

    kernel_s = sum(s for (op, prog), s in tr.kernel_s.items()
                   if prog == PROGRAM and op.startswith("nmg_"))
    if not kernel_s:
        return None
    eng = run.workload["engine"]
    steps = tr.program_calls[PROGRAM] * eng["decode_chunk"]
    least, _ = work.nmg_least_time(eng["max_slots"], run.config,
                                   run.config["serve_layout"], run.peaks)
    return 100.0 * steps * least / kernel_s
