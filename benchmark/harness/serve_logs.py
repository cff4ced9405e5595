"""What the program itself recorded about serving, as the per-layer readers
take it: the rows of ``starway_tpu.models.serving.step_log()`` and
``request_log()`` that fall in the run's window.  Both logs stamp with
``time.perf_counter``, which on Linux is the CLOCK_MONOTONIC of the
benchmark's ``time.monotonic`` window.  A program without the logs (a
parent commit from before they existed) gives empty lists: the readers
then return None and the metric is left out of the line."""

from __future__ import annotations


def _log(name: str) -> list:
    from starway_tpu.models import serving

    read = getattr(serving, name, None)
    return read() if callable(read) else []


def window_steps(obs: dict) -> list:
    """``step_log()`` rows of the steps that began inside the window (the
    process that holds the chip; the in-process cell reads its own)."""
    t0, t1 = obs["window"]
    return [r for r in _log("step_log") if t0 <= r["t0"] < t1]


def wire_rows(obs: dict) -> list:
    """Client rows of ``request_log()`` in the chip-less parent (written by
    ``RemoteGenerateSession.generate``) that carry the server's timing
    trailer, for the requests the run counted (joined by ``route``) and
    sent inside the window."""
    t0, t1 = obs["window"]
    counted = {r.get("route") for r in obs.get("requests") or []}
    return [r for r in _log("request_log")
            if r.get("side") == "client" and r.get("server_us")
            and r.get("t_first_rx") is not None
            and r.get("route") in counted and t0 <= r["t_send"] < t1]


def server_ttft_us(row: dict) -> int:
    """REQUEST received -> first TOKENS send posted, from the trailer."""
    us = row["server_us"]
    return (us["recv_submit"] + us["submit_admit0"] + us["admit0_first"]
            + us["first_post"])
