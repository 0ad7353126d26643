"""The fused fixed-point kernel's share of its roofline, in percent: the
least time the chip could take for the work of the traced engine steps
(``bench/work.py``: the larger of operations over the int8 peak and bytes
over HBM bandwidth, from ``bench/peaks.json``), over the summed device time
of the kernel's events in the trace.  Nothing when the kernel is absent."""

KERNEL = "_rnn_seq_fxp_call"


def read(m):
    t = m["trace"]
    kernel_s = sum(v for n, v in t["op_time"].items() if KERNEL in n)
    if kernel_s <= 0:
        return None
    p = m["peaks"]
    least = max(m["ops"] / p["int8_ops_per_s"], m["bytes"] / p["hbm_bytes_per_s"])
    # op_time is per chip; the work is over all chips
    return 100.0 * least / (kernel_s * m["chips"])
