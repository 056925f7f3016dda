"""Serving host path: percentage of the traced window in which no operation
ran on the chip while a program span (``repro.*``) was open -- the idle time
the program's own host work caused, and not the wait for arrivals, for the
admission window or for outputs (``program_spans.py``)."""


def read(window):
    s = getattr(window, "spans", None)
    if s is None or s.chips == 0:
        return None
    return 100.0 * s.host_idle_s / s.window_s
