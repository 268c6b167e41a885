"""Closed loop: ``in_flight`` clients, each with one request outstanding,
each sending its next request as soon as its last one is answered.

Requests answered by the same batch are answered at the same moment, so
their clients send their next requests together: the loop waits for the
oldest outstanding request, takes every other one that is done by then
(``GWServer.poll``), and only then submits all their successors, back to
back. After ``seconds`` it sends nothing more and drains what is in
flight, so the window ends on a whole batch.
"""
import collections
import time

from harness import Done


def widths(traffic: dict, config) -> list:
    """Lane widths a flush can have: powers of two (at least 2, the
    server's floor) up to the most requests one bucket can hold."""
    from repro.serve.batching import next_pow2

    widest = next_pow2(min(config.max_batch, traffic["in_flight"]))
    return sorted({next_pow2(k) for k in range(1, widest + 1)})


def run(server, client, traffic: dict, seconds: float,
        clock=time.perf_counter):
    """(window start, [Done]) on the host's ``clock``."""
    pending = collections.deque()
    done = []
    nxt = 0

    def send():
        nonlocal nxt
        p, s, k = client.request(nxt)
        ts = clock()
        pending.append((server.submit(p, s, key=k), nxt, ts, clock()))
        nxt += 1

    def answer(item):
        res, err = _result(server, item[0])
        done.append(Done(item[1], item[2], clock(), res, err,
                         admitted=item[3]))

    t0 = clock()
    for _ in range(traffic["in_flight"]):
        send()
    while pending:
        answer(pending.popleft())
        answered = 1
        for item in list(pending):
            if server.poll(item[0]) == "done":
                pending.remove(item)
                answer(item)
                answered += 1
        if clock() - t0 < seconds:
            for _ in range(answered):
                send()
    return t0, done


def _result(server, rid):
    """(result, None), or (None, the exception) for a failed request."""
    try:
        return server.result(rid), None
    except Exception as e:  # noqa: BLE001 -- a failed request counts
        return None, e
