"""The timing loop: one client that keeps up to `ahead` requests in
flight.

Request i is sent; then every request at the head of the queue that
has completed is handed on, and while more than `ahead` are still
outstanding the oldest is waited for. With `ahead` 0 each request is
sent only after the previous one has completed (a closed loop). With
more, the device has queued work to run while the host stands still,
so a stall of the host's clock weighs in the window only where it
outlasts the queue. The queue holds only what the device has not yet
finished, so its results take no more memory than that.

The window closes so: once `seconds` have passed, nothing more is
sent, every request that was sent is waited for, and the clock is read
after that wait. All of that work counts, over all of that time, so no
unfinished request is counted and a stall that runs to the window's
end still counts as time.

Host annotations (`bench.window`, and inside it `bench.dispatch` and
`bench.wait`) are no-ops unless the profiler is on. With it on they let
`bench.trace` say what the host was doing in each idle gap of the
device.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Window:
    requests: int
    seconds: float           # from the first send to the last completion
    dispatch_seconds: float  # summed over requests: the call's return


def run(send: Callable[[int], Any], wait: Callable[[Any], None],
        ready: Callable[[Any], bool], done: Callable[[int, Any], None],
        seconds: float, ahead: int = 0) -> Window:
    """Send requests 0, 1, ... with up to `ahead` outstanding beyond
    the newest; hand each to `done` once `wait` has seen it complete.
    `send` returns once the request is dispatched; `wait` blocks until
    its results are ready; `ready` says, without blocking, whether they
    are."""
    if ahead < 0:
        raise ValueError(f"ahead must be 0 or more, not {ahead}")
    clock = time.perf_counter
    queue: collections.deque = collections.deque()
    dispatch = 0.0
    i = 0

    def retire():
        j, out = queue.popleft()
        with TraceAnnotation("bench.wait"):
            wait(out)
        done(j, out)

    with TraceAnnotation("bench.window"):
        t0 = clock()
        while True:
            t = clock()
            with TraceAnnotation("bench.dispatch"):
                out = send(i)
            dispatch += clock() - t
            queue.append((i, out))
            i += 1
            while queue and (len(queue) > ahead or ready(queue[0][1])):
                retire()
            if clock() - t0 >= seconds:
                break
        while queue:
            retire()
        elapsed = clock() - t0
    return Window(requests=i, seconds=elapsed, dispatch_seconds=dispatch)
