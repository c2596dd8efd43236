"""setup_s: process start to window open, compilation and warm-up
included (host clock)."""


def read(run):
    return run.setup_s
