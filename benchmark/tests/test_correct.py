"""``correct`` has to be able to come out false.

1. The controls, at a size a test run can hold: the lower-precision
   reference fails a limit that the served tokens keep (serving); a
   broken guarantee is counted (transport).  The same readings at the
   cells' own sizes were taken on the chip (PERF.md section 2).
2. The rest of a run with the timed path broken underneath (the harness's
   look for a chip skipped): a token altered where it is produced, a byte
   altered where it is sent: ``correct`` comes out false.
"""

import time


from benchmark import run as R
from benchmark.harness import spec as S, traffic as T


def ctx_for(workload: str, seed: int, seconds: float) -> dict:
    args = R.parse(["--workload", workload, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", "0", "--no-chip"])
    ctx = R.context(args)
    ctx["t_start"] = time.monotonic()
    return ctx


def test_lower_precision_control_fails_the_limit_the_program_keeps():
    ctx = ctx_for("mistral7b.chat_closed", 2147480000, 2.0)
    ctx["config"]["correct"]["sample_requests"] = 8
    runner = S.load_runner("serve")
    w = runner.inproc_window(ctx)
    ref = S.load_reference("mistral7b")
    limits, sv = ctx["config"]["correct"], ctx["config"]["serve"]
    out_to = max(o for _p, o in T.request_set(ctx["traffic"]))
    served = ref.served_gaps(ctx["config"], 2147480000, w["sample"], sv["max_len"], out_to)
    control = ref.control_gaps(ctx["config"], 2147480000, w["sample"], sv["max_len"],
                               out_to, limits["control"])
    assert served["finite"] and served["tokens"] >= 40
    assert served["gap_mean"] <= limits["gap_mean_limit"]
    assert served["gap_max"] <= limits["gap_max_limit"]
    assert (control["gap_mean"] > limits["gap_mean_limit"]
            or control["gap_max"] > limits["gap_max_limit"])


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from starway_tpu.models.serving import SlotServer

    produce = SlotServer._run_chunk

    def altered(self, sub):
        toks, mask = produce(self, sub)
        return (toks + 1) % self.cfg.vocab_size, mask

    sound = S.load_runner("serve").run(ctx_for("mistral7b.chat_closed", 11, 2.0))
    assert sound["correct"] and sound["attempted"] > 0
    monkeypatch.setattr(SlotServer, "_run_chunk", altered)
    broken = S.load_runner("serve").run(ctx_for("mistral7b.chat_closed", 11, 2.0))
    assert broken["attempted"] > 0 and not broken["correct"]


def test_a_chunk_left_out_of_the_batch_is_not_correct(monkeypatch):
    """A step that returns part of its state unchanged: one slot's tokens
    are never harvested, so its request never gets its tokens."""
    from starway_tpu.models.serving import SlotServer

    produce = SlotServer._run_chunk

    def partial(self, sub):
        toks, mask = produce(self, sub)
        return toks, mask.at[::2, 0].set(False)

    monkeypatch.setattr(SlotServer, "_run_chunk", partial)
    out = S.load_runner("serve").run(ctx_for("mistral7b.chat_closed", 12, 2.0))
    assert not out["correct"]


def test_transport_control_breaks_a_guarantee():
    """The configuration states: after aflush every byte sent is in the
    receiver's buffer.  One byte short is counted; so is a stale round."""
    ref = S.load_reference("hbm_duplex")
    sent = ref.chunk(2 ** 31 + 5, 0, 1, 3, 7, 65536)
    assert ref.mismatched_bytes(sent, 2 ** 31 + 5, 0, 1, 3, 7) == 0
    assert ref.header_ok(sent[:16], 7, 3, 2 ** 31 + 5, 0, 1)
    short = sent.copy()
    short[40000] ^= 1
    assert ref.mismatched_bytes(short, 2 ** 31 + 5, 0, 1, 3, 7) == 1
    assert not ref.header_ok(sent[:16], 8, 3, 2 ** 31 + 5, 0, 1)   # stale round
    assert not ref.header_ok(sent[:16], 7, 4, 2 ** 31 + 5, 0, 1)   # wrong tag match
    assert ref.mismatched_bytes(sent, 2 ** 31 + 5, 1, 0, 3, 7) > 60000  # wrong sender


def test_a_byte_altered_where_it_is_sent_is_not_correct(monkeypatch):
    import starway_tpu as sw

    sound = S.load_runner("transport").run(ctx_for("hbm_duplex.a2a_16m_x4", 5, 1.0))
    assert sound["correct"] and sound["attempted"] > 0
    send = sw.Client.asend

    def altered(self, buffer, tag, *a, **kw):
        if hasattr(buffer, "at"):
            buffer = buffer.at[4096].add(1)
        return send(self, buffer, tag, *a, **kw)

    monkeypatch.setattr(sw.Client, "asend", altered)
    broken = S.load_runner("transport").run(ctx_for("hbm_duplex.a2a_16m_x4", 5, 1.0))
    assert broken["attempted"] > 0 and not broken["correct"]
