"""Execution: program dispatches (``executor.dispatch_count``) over the
window per request completed in it."""


def read(window):
    done = len(window.completed)
    return window.counters["dispatches"] / done if done else None
