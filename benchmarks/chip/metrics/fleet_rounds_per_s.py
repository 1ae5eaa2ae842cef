"""Communication rounds completed in the window over the window's wall time."""


def read(run):
    return run["rounds"] / run["window_s"]
