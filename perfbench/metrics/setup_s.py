"""Process start to the window's first instant: runtime, weights, compile
or cache load, warm-up, ramp."""


def read(run):
    return run.get("setup_s")
