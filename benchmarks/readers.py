"""What the metric readers under ``metrics/`` share. A reader is a file
``metrics/<metric>.py`` with the constants ``LAYER``, ``UNIT``,
XX
same, and a test holds them together) and one function ``read(run)``
that returns the number, or None where the run has nothing to read it
from. ``run`` is the dict a runner returns, with ``trace`` (the reduced
device trace, ``xplane.reduce``) added where one was taken.
"""
from __future__ import annotations

from . import common, xplane


def window_tokens(run: dict) -> int:
    """Tokens streamed to clients inside the window."""
    t0, t1 = run["t0"], run["t1"]
    return sum(1 for r in run["requests"] for t in r.token_times
               if t0 <= t < t1)


def ttft_ms(run: dict) -> list:
    """First token minus due time, per request due in the window; a
    request with no first token misses with the longest wait there is
    (the end of the drain)."""
    out = []
    for r in run["in_window"]:
        due = run["t0"] + r.due
        first = r.token_times[0] if r.token_times else float("inf")
        out.append((first - due) * 1e3)
    return out


def token_gaps_ms(run: dict) -> list:
    """Gaps between consecutive tokens of one request whose later
    token arrived inside the window, pooled over requests."""
    t0, t1 = run["t0"], run["t1"]
    return [(b - a) * 1e3 for r in run["requests"]
            for a, b in zip(r.token_times, r.token_times[1:])
            if t0 <= b < t1]


def lateness_ms(run: dict) -> list:
    return [(r.sent - (run["t0"] + r.due)) * 1e3 for r in run["in_window"]
            if r.sent is not None]


def snapshot_step_median_ms(run: dict, stage: str):
    """``metrics_snapshot()['step_ms'][stage]`` at the window's end: the
    engine's sliding window of its last 2,048 calls of that stage."""
    snap = run["snap1"]["step_ms"].get(stage)
    return float(snap["p50"]) if snap and snap["count"] else None


def decode_steps(run: dict) -> int:
    return int(run["snap1"]["batch_occupancy"]["steps"]
               - run["snap0"]["batch_occupancy"]["steps"])


def window_occupancy(run: dict):
    """Mean live lanes per decode iteration inside the window, from
    the running mean at both ends."""
    a, b = run["snap0"]["batch_occupancy"], run["snap1"]["batch_occupancy"]
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return (b["mean"] * b["steps"] - a["mean"] * a["steps"]) / steps


def module_median_ms(run: dict, contains: str):
    if "trace" not in run:
        return None
    durs = xplane.module_durations_ms(run["trace"], contains)
    return common.median(durs) if durs else None


def device_idle_pct(run: dict):
    if "trace" not in run:
        return None
    tr = run["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mosaic_seconds(run: dict) -> float:
    """Summed device time of the Mosaic (Pallas) custom calls in the
    traced window, by the names the trace prints for them."""
    return sum(v["seconds"] for name, v in run["trace"]["op_totals"].items()
               if is_mosaic_call(name))


def is_mosaic_call(name: str) -> bool:
    """An ``XLA Ops`` event of a Pallas kernel: its HLO text is a
    ``custom-call`` whose target is Mosaic's (looked at by hand in the
    train cell's trace, PR 23: four a layer, ``closed_call``,
    ``rematted_computation`` and two ``checkpoint``; the other
    custom-calls of the program are layout markers of no duration)."""
    return xplane.opcode(name) == "custom-call" \
        and 'custom_call_target="tpu_custom_call"' in name
