"""Device: percentage of the traced window in which no XLA operation ran on
the chip (1 - union of the device-op intervals / window)."""


def read(window):
    t = window.trace
    if t is None or t.chips == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
