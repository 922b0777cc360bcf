"""Knuth-Yao walks the round programs ran per site update, over the
engine rounds that start in the window: the sum of the ``round`` spans'
``ky_lanes`` over the sum of their ``site_updates`` (every lane, vacant
ones included).  A sweep that walks one colour at a time reads just
above 1 (the pad column of an odd width, the clamped sites); one that
walks the whole lattice for each colour reads about 2.  None where the
rounds carry no such counts."""


def read(run):
    lanes = updates = 0
    for ev in run.events:
        if ev.get("ph") != "X" or ev.get("name") != "round":
            continue
        args = ev.get("args", {})
        if ("ky_lanes" in args and run.t0
                <= run.tel_t0 + ev["ts"] * 1e-6 < run.t1):
            lanes += args["ky_lanes"]
            updates += args["site_updates"]
    return lanes / updates if updates else None
