"""The device's idle share of the traced window: 100 x (1 - the union of
the intervals in which an operation ran, over the window), %."""


def read(rec):
    dev = rec.device
    if not dev or not dev["window_s"] or not dev["busy_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
