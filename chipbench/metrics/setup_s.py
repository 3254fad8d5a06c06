"""Set-up: process start to the first timed step (inputs made on the
device, compile or compile-cache loads, warm-up).  Host clock."""


def read(run):
    return run["setup_s"]
