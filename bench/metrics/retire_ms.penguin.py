"""Mean ``retire`` span, per query retired in the window: the final
diagnostics of every query site and the answer built from its counts.
Read from the program's spans; None where it records no ``retire``."""
import numpy as np


def read(run):
    durs = []
    for ev in run.events:
        if ev.get("ph") == "X" and ev.get("name") == "retire":
            a = run.tel_t0 + ev["ts"] * 1e-6
            if run.t0 <= a < run.t1:
                durs.append(ev["dur"] * 1e-6)
    return float(np.mean(durs) * 1e3) if durs else None
