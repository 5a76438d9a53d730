"""Device idle share of the traced window, %: 1 - busy union / window, mean over chips."""


def read(summary, ctx):
    if summary is None or summary.window_s <= 0 or summary.busy_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
