"""Arithmetic the per-layer metric readers share."""
from __future__ import annotations

STAGE_SPANS = ("ship", "exec", "install")


def runtime_self_ms(obs, label: str):
    calls = obs.calls_of(label)
    if not calls:
        return None
    own = sum(c.wall - obs.span_cover(c.run_id, STAGE_SPANS)
              for c in calls)
    return 1e3 * own / len(calls)


def span_ms(obs, label: str, names):
    calls = obs.calls_of(label)
    if not calls:
        return None
    return 1e3 * sum(obs.span_seconds(c.run_id, names)
                     for c in calls) / len(calls)


def idle_pct(obs):
    tr = obs.trace
    if tr is None or tr.window_s <= 0 or tr.n_devices == 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
