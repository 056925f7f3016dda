"""Execution: mean host milliseconds of one ``repro.dispatch`` span in the
traced window (one task of a group in the executor: residency walk,
parameter gather, the compiled call, activation caching)."""


def read(window):
    s = getattr(window, "spans", None)
    d = s.spans.get("dispatch") if s is not None else None
    return d.total_s / d.count * 1e3 if d is not None and d.count else None
