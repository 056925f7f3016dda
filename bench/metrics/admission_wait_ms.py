"""Admission: mean time a request waited in the session's queue before the
scheduling policy admitted it, in milliseconds, from the session's
``wait_sum`` and ``requests_admitted`` counters over the window."""


def read(window):
    admitted = window.counters["requests_admitted"]
    return window.counters["wait_sum"] / admitted * 1e3 if admitted else None
