"""Least time of the traced decode calls over their device time, %, with
the held experts a call reads taken from the window's counted touches per
call (``moe_expert_touches`` over ``moe_decode_calls_counted``, per layer)
in place of the uniform expectation: the expert layer's roofline share,
read at the level of the whole decode program."""
import numpy as np

from bench import costs, readers


def read(record):
    c = record["counters"]
    calls = c.get("moe_decode_calls_counted")
    p, t, peaks = readers._program(record, "decode"), record.get("trace"), \
        record.get("peaks")
    if not calls or not p or not peaks:
        return None
    lo, hi = t["host_t0"], t["host_t1"]
    lives = [live for ts, live in record["decode_calls"]
             if lo <= ts <= hi and live]
    if not lives:
        return None
    m, fam = record["model"], readers.family(record)
    touched = c["moe_expert_touches"] / calls / m["n_layers"]
    least = np.mean([costs.least_time(*fam.decode_call(m, live, touched),
                                      peaks) for live in lives])
    return 100.0 * least * p[0] / p[1]
