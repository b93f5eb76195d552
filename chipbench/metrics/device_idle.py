"""Share of the traced window in which no op ran on the device."""


def read(rec):
    red = rec["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
