"""Scheduler: wall milliseconds per admitted request inside
``SlotServer._admit`` (ends in the admit program's result, so it holds the
prefill), closed-loop cell.  Mean over the window.  Moves ``tok_s``."""


def read(obs):
    spans, (t0, t1) = obs.get("spans"), obs["window"]
    if spans is None:
        return None
    admit_s, admits = spans.total("admit", t0, t1)
    return admit_s / admits * 1e3 if admits else None
