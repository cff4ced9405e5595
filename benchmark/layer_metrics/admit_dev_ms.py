"""Model (models/llama.py, generate.py): device milliseconds of one
admission: seconds over executions of the programs the profiler's ``XLA
Modules`` line names ``jit_serve_admit*`` (the program gives its admit
programs that name; one per prompt bucket) in the traced part of the
window.  The host's ``admit_ms`` around the same call holds it.  Moves
``tpot_p95_ms``."""

PREFIX = "jit_serve_admit"


def read(obs):
    modules = (obs.get("trace") or {}).get("modules") or {}
    runs = [(n, s) for name, (n, s) in modules.items()
            if name.startswith(PREFIX)]
    count = sum(n for n, _s in runs)
    return sum(s for _n, s in runs) / count * 1e3 if count else None
