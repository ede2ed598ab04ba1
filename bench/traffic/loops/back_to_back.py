"""One caller repeats one whole operation (``system.join()``) until the
window is over. Mix keys: ``loop`` alone.

A window starts with the first operation and ends with the last one that
was started before ``seconds`` had passed, so every operation counted ran
whole inside it."""
from __future__ import annotations


def drive(mix: dict, system, seconds: float) -> list[dict]:
    """Each record has the operation's ``start`` and ``end`` on the host
    clock and the requests it ``attempted``."""
    out = [system.join()]
    while out[-1]["end"] - out[0]["start"] < seconds:
        out.append(system.join())
    return out
