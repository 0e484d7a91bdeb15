"""Share of the traced window in which no device operation ran (%)."""


def read(ctx):
    red = ctx["trace"]
    if red["window_ns"] <= 0 or red["busy_ns"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
