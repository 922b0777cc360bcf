"""Share of the device's idle time, in the traced part of the window,
that no span of the dispatcher thread covers (``await_work``,
``group_start``, the groups' ``round``, ``deliver``, ``admit``): what
the program's spans leave unexplained.  None without a device trace or
where the program records no ``await_work``."""
from harness import layers, trace

DISPATCHER = ("await_work", "group_start", "round", "deliver", "admit")


def read(run):
    dev = run.device()
    if dev is None or not any(ev.get("name") == "await_work"
                              for ev in run.events):
        return None
    busy = layers.busy_intervals(dev)
    end = layers.traced_end(run, dev)
    idle = (end - run.t0) - trace.busy_s(busy, run.t0, end)
    if idle <= 0:
        return None
    host = [(ev["name"], run.tel_t0 + ev["ts"] * 1e-6, ev["dur"] * 1e-6)
            for ev in run.events
            if ev.get("ph") == "X" and ev.get("name") in DISPATCHER]
    unexplained = (end - run.t0) - trace.busy_s(busy + host, run.t0, end)
    return 100.0 * unexplained / idle
