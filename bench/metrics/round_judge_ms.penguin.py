"""Mean, over the engine rounds that start in the window, of the
``judge`` span's self time: folding the round's counts into each query
and its retirement check, less the ``retire`` spans inside it (building
answers).  Read from the program's spans; None where it records no
``judge``."""
import numpy as np


def _spans(run, name):
    """``(start, end, tid, args)`` of the program's spans named ``name``
    on the monotonic clock."""
    out = []
    for ev in run.events:
        if ev.get("ph") == "X" and ev.get("name") == name:
            a = run.tel_t0 + ev["ts"] * 1e-6
            out.append((a, a + ev["dur"] * 1e-6, ev["tid"],
                        ev.get("args", {})))
    return out


def read(run):
    judges = [s for s in _spans(run, "judge") if run.t0 <= s[0] < run.t1]
    if not judges:
        return None
    retires = _spans(run, "retire")
    self_s = [b - a - sum(r1 - r0 for r0, r1, rt, _ in retires
                          if rt == tid and a <= r0 and r1 <= b)
              for a, b, tid, _ in judges]
    return float(np.mean(self_s) * 1e3)
