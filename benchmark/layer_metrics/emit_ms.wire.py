"""Remote serving (models/remote_serving.py): milliseconds the bridge holds
a request's first token: from the token being a host int inside ``step()``
until its TOKENS send is posted after ``step()`` has returned
(``first_post``).  Median over the window's requests.  Source: the done
frame's timing trailer.  Moves ``ttft_p95_ms``."""

import statistics

from benchmark.harness.serve_logs import wire_rows


def read(obs):
    rows = wire_rows(obs)
    if not rows:
        return None
    return statistics.median(r["server_us"]["first_post"] / 1e3 for r in rows)
