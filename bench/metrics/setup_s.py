"""Seconds from process start to the first timed request: generation, the
program's layout step, device transfer, warm-up and compile-cache reads."""


def read(run):
    return run.setup_s
