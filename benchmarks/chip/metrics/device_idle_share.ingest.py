"""1 - (union of device-op intervals, mean over chips) / window, from the profiler trace of the window."""


def read(art):
    if art.trace is None or not art.writers:
        return None
    return art.trace["idle_share"]
