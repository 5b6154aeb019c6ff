"""Process start to the window's start: load, compile, warm-up, s."""


def read(run):
    return run.setup_s
