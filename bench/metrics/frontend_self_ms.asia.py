"""Median, over the front end's ``request`` spans that end in the
window, of the request (body or frame in hand to response written)
less the engine ``query`` span with the same ``qid`` (submit to
retirement): the front end's own time, parse, routing, the wait to be
resolved on the event loop, encoding and the socket write.  None where
the program records no ``request``."""
import numpy as np


def read(run):
    query, requests = {}, []
    for ev in run.events:
        if ev.get("ph") != "X":
            continue
        if ev.get("name") == "query":
            query[ev.get("args", {}).get("qid")] = ev["dur"] * 1e-6
        elif ev.get("name") == "request":
            end = run.tel_t0 + (ev["ts"] + ev["dur"]) * 1e-6
            if run.t0 <= end <= run.t1:
                requests.append((ev["args"]["qid"], ev["dur"] * 1e-6))
    own = [d - query[q] for q, d in requests if q in query]
    return float(np.median(own) * 1e3) if own else None
