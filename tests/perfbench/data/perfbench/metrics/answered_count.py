"""A test-only metric: requests answered in the window."""


def read(run):
    return float(run.window.answered_in_window)
