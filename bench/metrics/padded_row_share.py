"""Planning: percentage of the batch rows dispatched in the window that were
padding, from the executor's ``rows_padded`` and ``rows_dispatched``
counters (each counted once per task of a group)."""

COUNTERS = {"rows_dispatched": "engine.executor.rows_dispatched",
            "rows_padded": "engine.executor.rows_padded"}


def read(window):
    rows = window.counters.get("rows_dispatched")
    return 100.0 * window.counters["rows_padded"] / rows if rows else None
