"""Execution accounting: host milliseconds of ``repro.predict`` self time
(the cost model's prediction after each group ran) per ``repro.group`` in the
traced window."""


def read(window):
    s = getattr(window, "spans", None)
    g = s.spans.get("group") if s is not None else None
    if g is None or not g.count:
        return None
    p = s.spans.get("predict")
    return (p.self_s if p is not None else 0.0) / g.count * 1e3
