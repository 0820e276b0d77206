"""What the program itself says of a job's start, for the ``startup.*``
readers under ``layer_metrics/`` (one call each): the Dashboard's start-up
monitors (``multiverso_tpu/dashboard.py``; ``docs/observability.md``,
"Start-up") and the compile account (``multiverso_tpu/compile_cache.py``).

Readers run in the run's own process after the window, so both are read
directly.  ``mv.shutdown()``, which the runner ``sgns_train`` calls before it
returns, resets the Dashboard: the monitors it cleared are still at hand
(``dashboard.ended()``).  The account is the whole process's, so what compiles
after the window (a few reductions the runners ask for) is in it.

A program without the monitor or the account (the parent of the PR that added
them) gives ``None``, and the readers leave their metric out.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["monitor_s", "compile_account"]


def monitor_s(*names: str) -> Optional[float]:
    """Seconds under the monitors ``names`` that ran, summed: the live one,
    else the one the last shutdown cleared."""
    from multiverso_tpu import dashboard

    live = dashboard.report(log=False)
    ended = getattr(dashboard, "ended", dict)()
    ran = [m for m in (live.get(n) or ended.get(n) for n in names)
           if m is not None and m.count]
    return sum(m.total_s for m in ran) if ran else None


def compile_account() -> Optional[dict]:
    """``compile_cache.account()`` once a program has been booked."""
    from multiverso_tpu import compile_cache

    account = getattr(compile_cache, "account", None)
    found = account() if account else None
    return found if found and found["programs"] else None
