"""The measured window: a closed loop of the traffic file's clients, each
submitting its next query through ``PlanClient.collect`` when its last reply
is back, on the connection it warmed. No query is submitted after
``seconds``; the window ends when the queries then in flight have replied or
timed out."""

import random
import threading
import time


class Record:
    """One timed submission."""
    __slots__ = ("client", "query", "submit", "reply", "table", "error",
                 "fell_back", "metrics", "trace")

    def __init__(self, client, query, submit):
        self.client, self.query, self.submit = client, query, submit
        self.reply = self.table = self.error = self.trace = None
        self.fell_back, self.metrics = [], {}

    @property
    def seconds(self):
        return self.reply - self.submit


def run(connect, plans, weights, clients, seconds, seed, warm_passes=1,
        warm_seconds=0.0, opened=None, want_trace=False, between=None):
    """``connect()`` gives a fresh PlanClient; ``plans[i]`` is a DataFrame.
    Each client first submits every plan through its own connection,
    untimed, ``warm_passes`` times and again until the connection is
    ``warm_seconds`` old: a shape's first executions on a server and on a
    connection run slower than its later ones (PERF.md section 5). When
    all are through, ``opened()`` runs
    and the window begins. ``between(records_of_client_0)`` runs in client 0
    before each of its timed submissions (the traced run starts and stops
    the profiler there). Returns the records in submit order, the window's
    first submit and last reply on ``time.perf_counter``, and the seconds
    of each warm-up submission."""
    records, warm, errors, lock = [], [], [], threading.Lock()
    clock = {}

    def open_window():
        if opened is not None:
            opened()
        clock["begin"] = time.perf_counter()

    gate = threading.Barrier(clients, action=open_window)

    def loop(k):
        rng = random.Random(seed * 1009 + k)
        client = connect()
        connected = time.perf_counter()
        mine = []
        try:
            try:
                passes = 0
                while (passes < warm_passes
                       or time.perf_counter() - connected < warm_seconds):
                    for plan in plans:
                        t0 = time.perf_counter()
                        client.collect(plan)
                        with lock:
                            warm.append(round(time.perf_counter() - t0, 3))
                    passes += 1
                gate.wait()
            except threading.BrokenBarrierError:
                return
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
                gate.abort()
                return
            deadline = clock["begin"] + seconds
            while True:
                if time.perf_counter() >= deadline:
                    return
                if k == 0 and between is not None:
                    between(mine)
                i = rng.choices(range(len(plans)), weights=weights)[0]
                rec = Record(k, i, time.perf_counter())
                try:
                    rec.table = client.collect(plans[i])
                    rec.reply = time.perf_counter()
                    rec.fell_back = list(client.last_fell_back)
                    rec.metrics = dict(client.last_metrics)
                    if want_trace:
                        rec.trace = client.last_trace()
                except Exception as e:      # counted as failed, never hidden
                    rec.reply = time.perf_counter()
                    rec.error = f"{type(e).__name__}: {e}"
                    client.close()
                    client = connect()
                mine.append(rec)
                with lock:
                    records.append(rec)
        finally:
            client.close()

    threads = [threading.Thread(target=loop, args=(k,), daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"a warm-up submission failed: {errors[0]}")
    if between is not None:
        between(None)           # the window is over: stop what still runs
    records.sort(key=lambda r: r.submit)
    first = records[0].submit if records else clock["begin"]
    last = max((r.reply for r in records), default=clock["begin"])
    return records, first, last, warm

