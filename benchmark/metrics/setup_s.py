"""Process start to the start of the timed window: table, frame, compile or
cache load, the warm-up fit.  Host clock."""


def read(run):
    return run["setup_s"]
