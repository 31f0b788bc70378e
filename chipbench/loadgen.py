"""The open-loop load generator, run as a child process of the harness.

    python chipbench/loadgen.py <host> <port>

It imports no jax and nothing of the program, so it never touches the chip
and takes no share of the server's interpreter lock.  It prints ``ready``,
reads one JSON line (window start ``t0`` on the monotonic clock, the jobs
with their send times, the poll interval and the drain deadline), then:

* a sender thread POSTs each job at ``t0 + t`` and records how late it
  sent and how long the POST took (a store hit resolves inside it);
* a poller thread GETs every job still open each ``poll_s`` seconds and
  records when it first saw the job in a terminal state.

At the deadline it stops and prints one JSON line of results.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time

TERMINAL = ("done", "failed", "cancelled")


class Client:
    """One keep-alive connection; reconnects once after an error."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.conn = None

    def call(self, method: str, path: str, body=None) -> dict:
        data = None if body is None else json.dumps(body).encode()
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, self.port,
                                                       timeout=60)
            try:
                self.conn.request(method, path, body=data,
                                  headers={"Content-Type":
                                           "application/json"})
                return json.loads(self.conn.getresponse().read())
            except (OSError, http.client.HTTPException):
                self.conn.close()
                self.conn = None
                if attempt:
                    raise
        raise AssertionError("unreachable")


def run(host: str, port: int, plan: dict) -> dict:
    t0, jobs = plan["t0"], plan["jobs"]
    poll_s, deadline = plan["poll_s"], plan["deadline"]
    recs = [{"t": j["t"], "state": "unsent"} for j in jobs]
    lock = threading.Lock()
    open_ids = {}                       # job id -> record index
    sent_all = threading.Event()

    def resolve(i: int, view: dict, seen: float) -> None:
        r = recs[i]
        r.update(state=view.get("state"), outcome=view.get("outcome"),
                 key=view.get("key"), error=view.get("error"),
                 deduped=view.get("deduped"), seen=seen - t0)

    def send() -> None:
        c = Client(host, port)
        for i, j in enumerate(jobs):
            due = t0 + j["t"]
            while True:
                wait = due - time.monotonic()
                if wait <= 0:
                    break
                time.sleep(wait)
            if time.monotonic() > deadline:
                break
            start = time.monotonic()
            try:
                view = c.call("POST", "/jobs", {"spec": j["spec"]})
            except (OSError, http.client.HTTPException, ValueError) as e:
                recs[i].update(state="unsent", error=repr(e))
                continue
            end = time.monotonic()
            r = recs[i]
            r.update(late=start - due, post_s=end - start, id=view.get("id"))
            if "id" not in view:
                r.update(state="refused", error=view.get("error"))
                continue
            if view.get("state") in TERMINAL:
                resolve(i, view, end)
            else:
                r["state"] = view.get("state")
                with lock:
                    open_ids[view["id"]] = i
        sent_all.set()

    def poll() -> None:
        c = Client(host, port)
        while time.monotonic() < deadline:
            start = time.monotonic()
            with lock:
                pending = list(open_ids.items())
            if not pending and sent_all.is_set():
                return
            for jid, i in pending:
                view = c.call("GET", f"/jobs/{jid}")
                if view.get("state") in TERMINAL:
                    resolve(i, view, time.monotonic())
                    with lock:
                        del open_ids[jid]
            time.sleep(max(poll_s - (time.monotonic() - start), 0.0))

    threads = [threading.Thread(target=send, name="loadgen-send"),
               threading.Thread(target=poll, name="loadgen-poll")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in recs:
        if r["state"] not in TERMINAL + ("refused",):
            r["state"] = "unresolved"
    late = sorted(r["late"] for r in recs if "late" in r)
    return {"jobs": recs,
            "lateness": {"sent": len(late),
                         "p50_s": late[len(late) // 2] if late else None,
                         "max_s": late[-1] if late else None}}


def main(argv) -> int:
    host, port = argv[1], int(argv[2])
    Client(host, port).call("GET", "/healthz")
    print("ready", flush=True)
    plan = json.loads(sys.stdin.readline())
    print(json.dumps(run(host, port, plan)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
