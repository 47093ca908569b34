"""Process start to the window's opening: imports, chip start-up, weights,
compiles (or their loads from the persistent cache), warm-up and the
traffic's lead-in."""


def read(run):
    return run.setup_s
