"""Median client latency (send to decoded answer) minus the median
engine ``query`` span (submit to delivery): what the front end, the
protocol and the socket add."""
from harness import layers, window


def read(run):
    client = [r["t_recv"] - r["t_send"] for r in run.due()
              if window.answered(r)]
    engine = [b - a for a, b, _ in run.spans("query")]
    c, e = layers.median_ms(client), layers.median_ms(engine)
    if c is None or e is None:
        return None
    return c - e
