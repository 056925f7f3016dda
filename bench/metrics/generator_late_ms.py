"""Load generator: how late the open loop sent its requests.

The 95th percentile over the window's requests of (send time - due time), in
milliseconds, on the host clock.  A starved generator shows here and is not
read as a fast server.  Closed loops have no due times: nothing to read.
"""
from bench.traffic import percentile


def read(window):
    late = [r.sent - r.due for r in window.requests if r.due is not None]
    return percentile(late, 95) * 1e3 if late else None
