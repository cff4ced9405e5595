"""Remote serving + transport (models/remote_serving.py, api.py): median,
over requests, of the client-side arrival of the first token minus the
server's ``on_tokens`` time for the same request.  ``time.monotonic`` is
CLOCK_MONOTONIC, shared by the processes of one Linux host.  It holds the
rest of the step the token was made in: the bridge sends after ``step()``
returns.  Moves ``ttft_p95_ms``."""

import statistics


def read(obs):
    child = obs.get("child")
    if not child or not child.get("server_first"):
        return None
    server = child["server_first"]
    gaps = [(r["first"] - server[r["route"]]) * 1e3 for r in obs["requests"]
            if r.get("route") in server and r["first"] is not None]
    return statistics.median(gaps) if gaps else None
