"""The measured window: whole calls, back to back, on the host's clock."""

from __future__ import annotations

import time


def run(session, seconds: float, annotate) -> tuple:
    """Start a new call while less than ``seconds`` have passed, always
    finish the one in progress.  Returns the calls as ``(start, end,
    passes)`` on ``time.perf_counter``, the last model, and the window's
    start."""
    calls, model = [], None
    t0 = time.perf_counter()
    while True:
        with annotate("fit.call"):
            start = time.perf_counter()
            model = session.call()
            end = time.perf_counter()
        with annotate("between_fits"):
            calls.append((start, end, session.passes(model)))
            if end - t0 >= seconds:
                return calls, model, t0
