"""Seconds from process start to the first timed request or step: weight
generation, conversion, compilation and warm-up."""


def read(run):
    return run.setup_s
