"""One closed-loop caller: it sends a batch of ``mix['batch']`` queries
(``system.send(system.draw(n))``), drawn before its timer starts, waits for
the answer, and sends the next, until the window is over. Mix keys:
``loop``, ``batch``.

A window starts with the first batch and ends with the last one that was
sent before ``seconds`` had passed, so every batch counted ran whole
inside it."""
from __future__ import annotations


def drive(mix: dict, system, seconds: float) -> list[dict]:
    """Each record has the batch's ``start`` and ``end`` on the host clock
    and the queries it ``attempted``."""
    b = int(mix["batch"])
    out: list[dict] = []
    while not out or out[-1]["end"] - out[0]["start"] < seconds:
        out.append(system.send(system.draw(b)))
    return out
