"""Random bits the Knuth-Yao walks consumed per site update, over the
engine rounds that start in the window: the sum of the ``round``
spans' ``bits`` over the sum of their ``site_updates`` (every lane,
vacant ones included, as an answer's ``bits_per_sample`` counts them).
None where the rounds carry no such counts."""


def read(run):
    bits = updates = 0
    for ev in run.events:
        if ev.get("ph") != "X" or ev.get("name") != "round":
            continue
        args = ev.get("args", {})
        if ("bits" in args and run.t0
                <= run.tel_t0 + ev["ts"] * 1e-6 < run.t1):
            bits += args["bits"]
            updates += args["site_updates"]
    return bits / updates if updates else None
