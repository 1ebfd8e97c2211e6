"""The measured window, and the work done in it, from the scheduler's
counters.

``ServeStats.events`` holds ``(decode steps so far, tokens so far,
perf_counter)`` at every sync point; each timestamp follows
``block_until_ready``, so a window that starts and ends on events is
exact.  Tokens so far count one first token per prefill and one token per
live slot per decode step, so the prefills done by an event are its tokens
less the decode tokens of the steps before it.  Requests are admitted in
the order they were queued (every request arrives at once, first in first
out), so those prefills are the first ones of that order.

The window starts at the first event with every slot full and ends at the
first event ``seconds`` or more later, or at the first event after the
last admission (the queue is empty), whichever comes first.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Window:
    t0: float                # perf_counter of the first and last event
    t1: float
    steps: tuple[int, int]   # decode steps [first, last) in the window
    admitted: tuple[int, int]  # admission indices [first, last)
    tokens: int              # tokens emitted in the window
    order: tuple[int, ...]   # request ids in admission order

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def prefills(self) -> int:
        return self.admitted[1] - self.admitted[0]

    def prefilled(self) -> tuple[int, ...]:
        """Request ids prefilled inside the window."""
        return self.order[self.admitted[0]:self.admitted[1]]


def admission_order(results, requests) -> tuple[int, ...]:
    order = sorted(requests, key=lambda r: (results[r.rid].admitted_step, r.rid))
    steps = [results[r.rid].admitted_step for r in requests]
    if steps != sorted(steps):
        raise ValueError("requests were not admitted in the order queued")
    return tuple(r.rid for r in order)


def select(stats, results, requests, capacity: int, seconds: float) -> Window:
    ev = stats.events
    decoded = [0]
    for a in stats.active_per_step:
        decoded.append(decoded[-1] + a)
    prefills = [tok - decoded[steps] for steps, tok, _ in ev]
    finished = sorted(results[r.rid].finished_step for r in requests)

    def live(i: int) -> int:
        steps = ev[i][0]
        done = sum(1 for f in finished if 0 <= f <= steps) if i else 0
        return prefills[i] - done

    start = next((i for i in range(len(ev)) if live(i) >= capacity), None)
    if start is None:
        raise ValueError("the slots were never all full")
    t0 = ev[start][2]
    end = next((j for j in range(start + 1, len(ev))
                if ev[j][2] - t0 >= seconds or prefills[j] == len(requests)),
               len(ev) - 1)
    if end <= start:
        raise ValueError("the window holds no event after its start")
    return Window(t0=t0, t1=ev[end][2], steps=(ev[start][0], ev[end][0]),
                  admitted=(prefills[start], prefills[end]),
                  tokens=ev[end][1] - ev[start][1],
                  order=admission_order(results, requests))


def cached_per_step(win: Window, stats, results, requests) -> list[list[int]]:
    """For each decode step of the window, the positions already cached of
    each live slot; checked against the scheduler's live count per step."""
    s0, s1 = win.steps
    out: list[list[int]] = [[] for _ in range(s1 - s0)]
    for r in requests:
        a = results[r.rid].admitted_step
        p = len(r.prompt)
        for k in range(r.max_new - 1):
            j = a + k
            if s0 <= j < s1:
                out[j - s0].append(p + k)
    for j, c in enumerate(out):
        if len(c) != stats.active_per_step[s0 + j]:
            raise ValueError(f"step {s0 + j}: {len(c)} live slots reconstructed, "
                             f"{stats.active_per_step[s0 + j]} counted")
    return out
