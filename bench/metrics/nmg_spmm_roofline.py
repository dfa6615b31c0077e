"""Share of its roofline that the n:m:g kernel reaches inside the admission
prefill (``jit_run``): the least time of the work each prompt requires of
the configuration's n:m:g weights, summed over the prompts prefilled in
the traced window, over the device time of the kernel's events there."""

PROGRAM = "jit_run"


def read(run):
    tr = run.trace
    if tr is None or run.kind != "serve" or run.peaks is None:
        return None
    from bench.lib import work
    from bench.lib.stats import in_window

    kernel_s = sum(s for (op, prog), s in tr.kernel_s.items()
                   if prog == PROGRAM and op.startswith("nmg_"))
    if not kernel_s:
        return None
    least = sum(work.nmg_least_time(r["prompt_len"], run.config,
                                    run.config["serve_layout"],
                                    run.peaks)[0]
                for r in run.requests
                if r["delivered"] and in_window(run, r["delivered"][0]))
    return 100.0 * least / kernel_s
