"""Planning: host milliseconds the session spent in ``plan_groups``
(bucketing, padding, group ordering) per group executed, from the session's
``plan_seconds`` and ``groups_executed`` counters over the window."""


def read(window):
    groups = window.counters["groups_executed"]
    return window.counters["plan_seconds"] / groups * 1e3 if groups else None
