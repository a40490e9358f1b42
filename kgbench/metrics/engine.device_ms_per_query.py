"""Device time per executed row: the seconds in which an operation ran on
the device in the traced window, over the rows the server executed in it,
ms."""


def read(rec):
    dev = rec.device
    if not dev or not dev["busy_s"] or not dev.get("executed"):
        return None
    return dev["busy_s"] * 1e3 / dev["executed"]
