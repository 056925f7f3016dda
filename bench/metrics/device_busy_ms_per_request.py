"""Device: milliseconds of the traced window in which an operation ran on
the chip, per request completed in the window."""


def read(window):
    t, done = window.trace, len(window.completed)
    if t is None or t.chips == 0 or not done:
        return None
    return t.busy_s / done * 1e3
