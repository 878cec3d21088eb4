"""Arrivals ``closed_loop``: ``callers`` callers, each sending its next
request as soon as its last answer has come, until the window closes.

A request is due when its caller sends it. The mix's ``max_requests`` sets
how many requests are made in set-up; it has to exceed what the window can
answer, and a run that uses them all says so on standard error.
"""

import sys
import threading

import numpy as np


def due_times(mix: dict, seconds: float, rng) -> np.ndarray:
    return np.full(int(mix["max_requests"]), np.nan)


def drive(window, mix: dict, due_s) -> None:
    lock = threading.Lock()
    order = iter(range(len(due_s)))

    def caller():
        while window.now() < window.seconds:
            with lock:
                i = next(order, None)
            if i is None:
                print("closed_loop: max_requests used up inside the window", file=sys.stderr)
                return
            window.call(i)

    threads = [
        threading.Thread(target=caller, name=f"chipbench-caller-{k}")
        for k in range(int(mix["callers"]))
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
