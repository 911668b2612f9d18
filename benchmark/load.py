"""The one generator of load: it reads a traffic mix (a data file under
`traffic/`) and drives the engine with it for the window.

A mix lists its queries with whole-number weights.  A round holds each
query `weight` times; every round is put in an order drawn from the
seed, so every seed does the same work in another order.  `loop`:

    closed   `clients` sessions, each issuing its next query when its
             last has answered (a Spark job's stages wait for their
             `collect()`); a query is taken while the window is open and
             the one in flight is finished and counted
    open     the i-th query is due at (i // burst) * burst / rate_per_s
             seconds into the window, whatever the engine is doing;
             `clients` sessions serve them, every query due before the
             window closes is waited for, and its time counts from when
             it was due, so queueing shows

One client runs on the caller's thread; more run on threads of their
own.  A record is {"query", "asked", "start", "planned", "end"} on the
host's clock (`asked` is when the client wanted the answer from: its
own start in a closed loop, the due time in an open one; `cpu` is the
process's CPU seconds so far, all threads), with
"host_syncs" where one client makes the count a query's own.
"""
from __future__ import annotations

import random
import threading
import time
import traceback


def sequence(traffic: dict, seed: int):
    """The endless order of queries: rounds of the mix, each shuffled
    from the seed."""
    one_round = [int(q["query"]) for q in traffic["queries"]
                 for _ in range(int(q.get("weight", 1)))]
    if not one_round:
        raise SystemExit("benchmark: a traffic mix with no query")
    rng = random.Random(int(seed))
    while True:
        order = list(one_round)
        rng.shuffle(order)
        yield from order


def queries_of(traffic: dict) -> list:
    return sorted({int(q["query"]) for q in traffic["queries"]})


def drive(engine, traffic: dict, seed: int, seconds: float, tracer) -> dict:
    """The window.  Returns {"records", "answers": [(query, frame)],
    "errors", "host_syncs", "opened", "closed"}."""
    loop, clients = traffic["loop"], int(traffic.get("clients", 1))
    if loop not in ("closed", "open") or clients < 1:
        raise SystemExit(f"benchmark: loop {loop!r} with {clients} clients")
    rate = float(traffic["rate_per_s"]) if loop == "open" else None
    burst = int(traffic.get("burst", 1))
    order = sequence(traffic, seed)
    lock = threading.Lock()
    records, answers, errors, issued = [], [], [0], [0]
    solo = clients == 1
    syncs_open = engine.host_syncs()
    opened = time.perf_counter()
    deadline = opened + seconds

    def take():
        """The next query and when it is due, or None once the window
        has closed."""
        with lock:
            i = issued[0]
            due = opened + (i // burst) * burst / rate if rate \
                else time.perf_counter()
            if due >= deadline:
                return None
            issued[0] = i + 1
            return next(order), due

    def client():
        while (request := take()) is not None:
            query, due = request
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            s0 = engine.host_syncs() if solo else None
            try:
                answer, clk = engine.run(query, tracer.annotate)
            except Exception:
                traceback.print_exc()
                with lock:
                    errors[0] += 1
                continue
            finally:
                tracer.after_query(query)
            record = {"query": query, "asked": due if rate else clk[0],
                      "start": clk[0], "planned": clk[1], "end": clk[2],
                      "cpu": time.process_time()}
            if solo:
                record["host_syncs"] = engine.host_syncs() - s0
            with lock:
                records.append(record)
                answers.append((query, answer))

    if solo:
        client()
    else:
        threads = [threading.Thread(target=client, name=f"client-{i}")
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return {"records": records, "answers": answers, "errors": errors[0],
            "host_syncs": engine.host_syncs() - syncs_open,
            "opened": opened, "closed": time.perf_counter()}
