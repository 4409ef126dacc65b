"""The load generator: a process of its own, sockets and numpy only.

    python benchmarks/client.py <plan.json> <out.jsonl>

Started by run.py once the server is warm. It never imports jax or
cockroach_tpu, so its work does not sit on the server's interpreter lock.
Protocol on stdin/stdout, one line each way:

    -> READY                 every client is connected and has sent its
                             warm-up statements
    <- GO <seconds>          the window opens now
    -> DONE                  every client has finished; out.jsonl is written

Closed loop: a client sends its next statement when the last one's
ReadyForQuery has been read. A statement in flight at the deadline is
finished and counted. Each record carries the client clock's send and done
times relative to GO, the answer, and the error if there was one.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import traffic  # noqa: E402
from pgclient import PgClient  # noqa: E402


READY_TIMEOUT_S = 1500  # every client connected and warm (a cold cache compiles)
DRAIN_TIMEOUT_S = 900  # past the deadline, for statements in flight


def _client(cid: int, plan: dict, mix: dict, ready: threading.Barrier,
            go: threading.Event, clock: dict, out: list, errs: list):
    try:
        stream = traffic.Stream(mix, plan["seed"], cid)
        conn = PgClient(plan["addr"])
        try:
            # this connection's own warm-up: every statement shape once
            for _j, _p, sql in traffic.Stream(mix, plan["seed"] + 1,
                                              cid).warmup():
                _n, _r, err = conn.query(sql)
                if err:
                    errs.append(f"client {cid} warm-up: {err}")
            ready.wait()
            go.wait()
            t0, deadline = clock["t0"], clock["t0"] + clock["seconds"]
            last_done = t0
            while True:
                j, p, sql = stream.next()
                ts = time.perf_counter()
                if ts >= deadline:
                    break
                names, rows, err = conn.query(sql)
                td = time.perf_counter()
                out.append({"c": cid, "t": j, "p": p, "s": ts - t0,
                            "d": td - t0, "gap": ts - last_done,
                            "names": names, "rows": rows, "err": err})
                last_done = td
        finally:
            conn.close()
    except BaseException as e:  # reported to the parent, which fails the run
        errs.append(f"client {cid}: {type(e).__name__}: {e}")
        try:
            ready.abort()
        except Exception:
            pass


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    mix = traffic.load_mix(plan["mix"])
    n = int(mix["clients"])
    ready, go = threading.Barrier(n + 1), threading.Event()
    clock: dict = {}
    outs: list[list] = [[] for _ in range(n)]
    errs: list[str] = []
    threads = [threading.Thread(
        target=_client, args=(c, plan, mix, ready, go, clock, outs[c], errs),
        daemon=True) for c in range(n)]
    for t in threads:
        t.start()
    try:
        ready.wait(timeout=READY_TIMEOUT_S)
    except threading.BrokenBarrierError:
        print("FAILED " + json.dumps(errs[:5]), flush=True)
        return 1
    print("READY", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "GO":
        return 1
    clock["seconds"] = float(line[1])
    clock["t0"] = time.perf_counter()
    go.set()
    for t in threads:
        t.join(timeout=clock["seconds"] + DRAIN_TIMEOUT_S)
    alive = [t for t in threads if t.is_alive()]
    with open(out_path, "w") as f:
        for o in outs:
            for rec in o:
                f.write(json.dumps(rec) + "\n")
        f.write(json.dumps({"errors": errs,
                            "stuck_clients": len(alive)}) + "\n")
    print("DONE", flush=True)
    return 1 if alive else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
