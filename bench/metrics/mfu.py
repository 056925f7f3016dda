"""Model step: the FLOPs the requests completed in the window require (each
tree node on their paths once, see ``bench/flops.py``) per second of the
window, as a percentage of the chips' bf16 peak.  Padded rows and
recomputation do not count.  In an open loop it follows the offered rate."""


def read(window):
    done = window.completed
    if not done:
        return None
    required = sum(r.flops for r in done)
    return 100.0 * required / window.seconds / (window.chips * window.peak["bf16_flops_per_s"])
