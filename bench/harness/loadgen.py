"""Load generator: a child process that never imports JAX (the chip
belongs to the serving process).

    python bench/harness/loadgen.py SPEC.json

SPEC holds the server's port, the window's length, how long to wait
for answers after it closes (``grace``), and a schedule from
``traffic.py``.  Once up, the process prints ``ready`` and reads the
window's start on the shared monotonic clock from its standard input.
Open loop: one WebSocket (``/v2/stream``); each request is sent at its
scheduled time whatever has come back.  Closed loop: one thread per
caller, each POSTing ``/v2/query`` and sending its next request when
the last is answered, until the window closes.  Prints one JSON line:
a record per request sent, with its scheduled, sent and received
times, and the parts of the answer the benchmark reads.
"""
from __future__ import annotations

import json
import os
import select
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "src"))

from repro.serve.client import ServeClient, ServeHTTPError  # noqa: E402


def _keep(resp: dict) -> dict:
    """The parts of a wire answer the benchmark reads."""
    d = resp.get("diagnostics") or {}
    return {"marginals": resp.get("marginals"),
            "n_node_samples": resp.get("n_node_samples"),
            "wall_s": resp.get("wall_s"),
            "ess": min(d.get("ess_bulk", 0.0), d.get("ess_tail", 0.0)),
            "converged": resp.get("converged")}


def _sleep_until(t: float) -> None:
    while True:
        dt = t - time.monotonic()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.05))


def closed_loop(spec: dict) -> list[dict]:
    t_start, t_end = spec["t_start"], spec["t_start"] + spec["seconds"]
    records: list[dict] = []
    lock = threading.Lock()

    def user(u: int, wires: list[dict]) -> None:
        client = ServeClient(port=spec["port"],
                             timeout=spec["seconds"] + spec["grace"])
        _sleep_until(t_start)
        for i, wire in enumerate(wires):
            t_send = time.monotonic()
            if t_send >= t_end:
                return
            rec = {"user": u, "i": i, "t_sched": t_send, "t_send": t_send,
                   "t_recv": None, "status": None, "answer": None}
            try:
                resp = client.query(wire)
                rec.update(t_recv=time.monotonic(), status=200,
                           answer=_keep(resp))
            except ServeHTTPError as exc:
                rec.update(t_recv=time.monotonic(), status=exc.status)
            except OSError:
                rec["status"] = "timeout"
            with lock:
                records.append(rec)
            if rec["status"] != 200:
                return
        with lock:
            records.append({"user": u, "exhausted": True})

    threads = [threading.Thread(target=user, args=(u, w), daemon=True)
               for u, w in enumerate(spec["schedule"]["users"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(spec["seconds"] + spec["grace"] + 30)
    return records


def open_loop(spec: dict) -> list[dict]:
    reqs = spec["schedule"]["requests"]
    t_start = spec["t_start"]
    deadline = t_start + spec["seconds"] + spec["grace"]
    records = [{"i": i, "t_sched": t_start + r["t"], "t_send": None,
                "t_recv": None, "status": None, "answer": None}
               for i, r in enumerate(reqs)]
    sock = socket.create_connection(("127.0.0.1", spec["port"]), timeout=30)
    client = ServeClient(port=spec["port"])
    client._ws_handshake(sock)
    sock.settimeout(None)
    sent_all = threading.Event()

    def sender() -> None:
        for rec, r in zip(records, reqs):
            _sleep_until(rec["t_sched"])
            wire = dict(r["wire"], id=rec["i"])
            rec["t_send"] = time.monotonic()
            client._ws_send(sock, json.dumps(wire).encode())
        sent_all.set()

    th = threading.Thread(target=sender, daemon=True)
    th.start()
    got = 0
    while got < len(records):
        left = deadline - time.monotonic()
        if left <= 0:
            break
        readable, _, _ = select.select([sock], [], [], min(left, 0.5))
        if not readable:
            continue
        resp = client._ws_recv_json(sock)
        t = time.monotonic()
        if resp is None:
            break
        rec = records[resp["id"]]
        status = resp.pop("status", 200)
        rec.update(t_recv=t, status=status,
                   answer=_keep(resp) if status == 200 else None)
        got += 1
    th.join(max(0.0, deadline - time.monotonic()) + 5)
    try:
        client._ws_send(sock, b"", opcode=0x8)
    except OSError:
        pass
    sock.close()
    return [r for r in records if r["t_send"] is not None]


def main() -> None:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    # the parent opens the window once this process is up, and sends
    # the window's start on the shared monotonic clock
    print("ready", flush=True)
    spec["t_start"] = float(sys.stdin.readline())
    loop = spec["schedule"]["loop"]
    records = open_loop(spec) if loop == "open" else closed_loop(spec)
    print(json.dumps({"records": records, "t_done": time.monotonic()}))


if __name__ == "__main__":
    main()
