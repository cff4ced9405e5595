"""Transport API (api.py) under the serving stream: milliseconds of a
request's time to first token that pass on the wire, both directions: the
client's ``t_first_rx - t_send`` less the server's REQUEST-received ->
first-TOKENS-posted (the trailer's first four words).  Two durations, each
on its own process's clock, so no clock is compared across processes.
Median over the window's requests.  Moves ``ttft_p95_ms``."""

import statistics

from benchmark.harness.serve_logs import server_ttft_us, wire_rows


def read(obs):
    rows = wire_rows(obs)
    if not rows:
        return None
    return statistics.median(
        (r["t_first_rx"] - r["t_send"]) * 1e3 - server_ttft_us(r) / 1e3
        for r in rows)
