"""The whole step's share of the chip's peak, in percent: the model's
operations for the sensor-timesteps served in the traced window
(``bench/work.py``), over the window's seconds, the chips and the int8 peak
(``bench/peaks.json``; the datapath is integer)."""


def read(m):
    t = m["trace"]
    if t["window_s"] <= 0 or not m["ops"]:
        return None
    return 100.0 * m["ops"] / (t["window_s"] * m["chips"] * m["peaks"]["int8_ops_per_s"])
