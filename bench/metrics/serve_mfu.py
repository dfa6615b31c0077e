"""Operations the served tokens of the window require, prefill and decode,
at the serving layout's density, over the window times the chip's peak."""


def read(run):
    if run.kind != "serve" or run.peaks is None:
        return None
    from bench.lib import work
    from bench.lib.stats import in_window

    cfg = run.config
    dens = work.serve_density(cfg["serve_layout"])
    flops = 0.0
    for r in run.requests:
        times = r["delivered"]
        if times and in_window(run, times[0]):
            flops += work.prefill_flops(cfg, r["prompt_len"], dens)
        for i, t in enumerate(times[1:], start=1):
            if in_window(run, t):
                flops += work.decode_flops(cfg, r["prompt_len"] + i - 1,
                                           dens)
    span = run.window[1] - run.window[0]
    return 100.0 * flops / (span * run.peaks["flops_bf16"] *
                            run.device["count"])
