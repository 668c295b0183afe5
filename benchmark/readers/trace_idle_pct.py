"""The device's idle share of the traced window: 1 - union of the device
operations' intervals / window, mean over the chips used."""


def read(args: dict, facts: dict):
    r = facts["reduced"]
    if r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
