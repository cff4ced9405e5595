"""Scheduler (models/serving.py): host milliseconds per ``SlotServer.step()``
that are neither blocked on the decode chunk's result nor inside ``_admit``
(which ends blocked on the admit program's result): the Python between the
programs.  Mean over the steps of the window.  Source: the benchmark's
wrappers around ``step``, ``_admit`` and ``_run_chunk`` on the instance.
Moves ``tok_s``."""


def read(obs):
    spans, (t0, t1) = obs.get("spans"), obs["window"]
    if spans is None:
        return None
    step_s, steps = spans.total("step", t0, t1)
    if not steps:
        return None
    wait_s, _ = spans.total("chunk_wait", t0, t1)
    admit_s, _ = spans.total("admit", t0, t1)
    return (step_s - wait_s - admit_s) / steps * 1e3
