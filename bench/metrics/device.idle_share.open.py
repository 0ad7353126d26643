"""Share of the traced window, in percent, in which no operation ran on the
device: ``100 * (1 - busy / window)``, busy being the union of the device's
operation intervals, averaged over the chips."""


def read(m):
    t = m["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
