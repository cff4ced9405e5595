"""Model (models/llama.py, generate.py): device milliseconds of one decode
step: the duration, in the profiler's trace, of the decode-chunk program
(the longest program started inside each ``_run_chunk`` call) over the
chunk's steps; mean over the chunks traced.  Moves ``tpot_p95_ms``."""


def read(obs):
    trace = obs.get("trace")
    runs = (trace or {}).get("longest_program_in", {}).get("chunk")
    if not runs:
        return None
    return sum(runs) / len(runs) / obs["config"]["serve"]["chunk"] * 1e3
