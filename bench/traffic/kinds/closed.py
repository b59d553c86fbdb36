"""Closed loop: ``clients`` callers, each with at most one request outstanding.

A caller sends its next request ``think_s`` seconds (default 0) after its
reply returns.  ``clients`` = 1 is one synchronous caller; ``clients`` =
twice the bucket is an offline backlog, refilled after every step.  The
window closes at the first step boundary at or after ``seconds``; callers
still queued then were never admitted and are not attempted.
"""


def check(traffic: dict) -> None:
    if int(traffic["clients"]) < 1:
        raise ValueError("a closed loop needs at least one client")


def run(loop, traffic: dict, seconds: float, seed: int) -> None:
    w, clock = loop.w, loop.clock
    clients, think = int(traffic["clients"]), float(traffic.get("think_s", 0))
    w.t0 = clock()
    with loop.span("submit"):
        for _ in range(clients):
            loop.submit(w.t0)
    ready: list = []          # due times of callers back from a reply
    while True:
        done = loop.step()
        if w.t_last - w.t0 >= seconds:
            w.t_end = w.t_last
            break
        now = clock()
        ready += [now + think] * len(done)
        ready.sort()
        while ready:
            if ready[0] > clock():
                if loop.pending():
                    break
                loop.wait_until(ready[0])
            with loop.span("submit"):
                due = ready.pop(0)
                w.late_s = max(w.late_s, clock() - due)
                loop.submit(due)
    w.backlog_end = loop.pending()
    for req in loop.engine.request_queue.pending:
        w.due.pop(req.uid, None)
