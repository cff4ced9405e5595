"""Scheduler behind the bridge (models/serving.py, remote_serving.py):
milliseconds a request waits before its admission begins: in the bridge's
queue while ``step()`` runs in the executor (``recv_submit``) and in
``SlotServer``'s queue (``submit_admit0``).  Median over the window's
requests.  Source: the done frame's timing trailer, measured on the
server's clock and read by the client.  Moves ``ttft_p95_ms``."""

import statistics

from benchmark.harness.serve_logs import wire_rows


def read(obs):
    rows = wire_rows(obs)
    if not rows:
        return None
    return statistics.median(
        (r["server_us"]["recv_submit"] + r["server_us"]["submit_admit0"]) / 1e3
        for r in rows)
