"""swcheck (starway_tpu/analysis) -- the static contract gate's own tests.

Two halves:

* HEAD is clean: every pass runs green against this checkout (the same
  invocation CI's ``swcheck`` job and release_smoke.sh step 1 make).
* Each rule actually fires: a minimal copy of the contract surface is
  seeded into tmpdir, one violation is mutated in, and the matching rule
  must report it with a real file:line anchor.  The six ISSUE-2 fixtures
  (bumped frame constant, changed shm offset, dropped timeout_s ABI arg,
  callback under lock, jax import in core/, reworded reason string) are
  all here, plus the waiver policy, the docstring frame table, the
  engine-version annotation, and the multi-GiB marker guard.

Violation payloads are embedded as *strings* so this file itself stays
clean under the very passes it tests.
"""

from __future__ import annotations

import re
import shutil
from pathlib import Path

import pytest

from starway_tpu import analysis

REPO = Path(__file__).resolve().parents[1]


def _seed(tmp_path: Path) -> Path:
    """Copy the minimal contract surface (core/, errors.py, the declared
    lint-surface extras, native/) into tmpdir so mutations never touch
    the real tree."""
    root = tmp_path / "repo"
    shutil.copytree(
        REPO / "starway_tpu" / "core", root / "starway_tpu" / "core",
        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "starway_tpu" / "errors.py").write_text(
        (REPO / "starway_tpu" / "errors.py").read_text())
    # metrics.py is part of the lint surface (base.LINT_EXTRA_FILES): a
    # seeded tree without it would trip the lint-coverage missing-file
    # check by design.
    (root / "starway_tpu" / "metrics.py").write_text(
        (REPO / "starway_tpu" / "metrics.py").read_text())
    (root / "native").mkdir()
    for name in ("sw_engine.h", "sw_engine.cpp"):
        (root / "native" / name).write_text(
            (REPO / "native" / name).read_text())
    return root


def _edit(root: Path, relpath: str, old: str, new: str) -> None:
    p = root / relpath
    text = p.read_text()
    assert old in text, f"fixture drift: {old!r} not in {relpath}"
    p.write_text(text.replace(old, new, 1))


def _findings(root: Path, rule: str) -> list:
    return [f for f in analysis.run_all(root) if f.rule == rule]


def _assert_caught(root: Path, rule: str, needle: str, in_file: str) -> None:
    hits = _findings(root, rule)
    assert hits, f"rule {rule} did not fire"
    hit = next((f for f in hits if needle in f.message), None)
    assert hit is not None, f"no [{rule}] finding mentions {needle!r}: {hits}"
    assert hit.line > 0 and hit.file.endswith(in_file), hit.render()
    assert f"{hit.file}:{hit.line}: [{rule}]" in hit.render()


# ------------------------------------------------------------- HEAD clean


def test_head_is_clean():
    findings = analysis.run_all(REPO)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_seeded_copy_is_clean(tmp_path):
    # The mutation fixtures below are only meaningful if the unmutated
    # copy passes: a dirty baseline would mask which rule fired.
    root = _seed(tmp_path)
    findings = analysis.run_all(root)
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------- the six ISSUE-2 violations


def test_bumped_frame_constant(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py", "T_DATA = 3", "T_DATA = 9")
    _assert_caught(root, "contract-frames", "T_DATA", "frames.py")


def test_changed_shm_offset(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/shmring.py", "OFF_HEAD = 64", "OFF_HEAD = 128")
    _assert_caught(root, "contract-shm", "OFF_HEAD", "shmring.py")


def test_dropped_timeout_abi_arg(tmp_path):
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "native.py"
    text = p.read_text()
    new = re.sub(
        r"(_RECV_CB, _FAIL_CB, ctypes\.c_void_p,\s*)ctypes\.c_double,",
        r"\1", text, count=1)
    assert new != text, "fixture drift: sw_recv argtypes shape changed"
    p.write_text(new)
    _assert_caught(root, "contract-abi", "sw_recv", "native.py")
    hit = next(f for f in _findings(root, "contract-abi") if "sw_recv" in f.message)
    assert "8 argtypes" in hit.message and "9 parameters" in hit.message


def test_callback_under_lock(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_lock.py").write_text(
        "def _run_fires(fires):\n"
        "    pass\n"
        "\n"
        "class W:\n"
        "    def bad(self, fires, fail):\n"
        "        with self.lock:\n"
        "            _run_fires(fires)\n"
        "            fail('boom')\n"
        "    def good(self, fires, fail):\n"
        "        with self.lock:\n"
        "            fires.append(lambda: fail('deferred is fine'))\n"
        "        _run_fires(fires)\n"
    )
    hits = _findings(root, "callback-under-lock")
    assert {f.line for f in hits} == {7, 8}, hits
    _assert_caught(root, "callback-under-lock", "_run_fires", "_seeded_lock.py")
    _assert_caught(root, "callback-under-lock", "`fail(...)`", "_seeded_lock.py")


def test_import_jax_in_core(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_jax.py").write_text(
        "import jax\n"
        "from jax.experimental import transfer\n"
    )
    hits = _findings(root, "layering-jax")
    assert {f.line for f in hits} == {1, 2}, hits
    _assert_caught(root, "layering-jax", "import jax", "_seeded_jax.py")


def test_reshard_imported_from_core(tmp_path):
    """layering-reshard row 1 (ISSUE 12): reshard/ sits ABOVE core/ --
    any core/ module importing the schedule layer, absolutely or
    relatively, is a finding."""
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_reshard.py").write_text(
        "import starway_tpu.reshard\n"
        "from starway_tpu.reshard import plan\n"
        "from ..reshard import tags\n"
        "from starway_tpu import reshard\n"
        "from .. import reshard\n"
    )
    hits = _findings(root, "layering-reshard")
    assert {f.line for f in hits} == {1, 2, 3, 4, 5}, hits
    _assert_caught(root, "layering-reshard", "ABOVE core/",
                   "_seeded_reshard.py")


def test_jax_bound_outside_reshard_adapter(tmp_path):
    """layering-reshard row 2: under reshard/ only api.py (the jax
    adapter) may import jax -- the planner/executor stay jax-free."""
    root = _seed(tmp_path)
    pkg = root / "starway_tpu" / "reshard"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "plan.py").write_text(
        "import jax\n"
        "from jax.sharding import NamedSharding\n"
    )
    # The adapter itself is exempt: jax is its whole job.
    (pkg / "api.py").write_text("import jax\n")
    hits = _findings(root, "layering-reshard")
    assert {(f.file.rsplit('/', 1)[-1], f.line) for f in hits} == \
        {("plan.py", 1), ("plan.py", 2)}, hits
    _assert_caught(root, "layering-reshard", "api.py", "plan.py")


def test_reworded_reason_string(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/errors.py",
          'REASON_TIMEOUT = "Operation timed out (deadline exceeded before completion)"',
          'REASON_TIMEOUT = "Operation exceeded its deadline"')
    hits = _findings(root, "contract-reason")
    # Both sub-checks fire: the stable "timed out" keyword is gone AND the
    # literal no longer matches the C++ engine's kTimedOut.
    assert any("stable keyword" in f.message for f in hits), hits
    assert any("kTimedOut" in f.message for f in hits), hits
    _assert_caught(root, "contract-reason", "REASON_TIMEOUT", "errors.py")


# ------------------------------------------------- remaining rule surface


def test_blocking_call_on_engine_thread(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_sleep.py").write_text(
        "import time\n"
        "def spin():\n"
        "    time.sleep(0.5)\n"
    )
    _assert_caught(root, "blocking-call", "time.sleep", "_seeded_sleep.py")


def test_garbled_doc_table(tmp_path):
    # Re-introduce the pre-fix bug this PR repaired: the HELLO_ACK row
    # losing its column separator must be caught, so the docstring table
    # can never silently drift from the T_* constants again.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py", "HELLO_ACK 0", "HELLO_ACK0 ")
    hits = _findings(root, "contract-doctable")
    assert any("HELLO_ACK0" in f.message for f in hits), hits
    assert any("missing from the docstring table" in f.message for f in hits), hits


def test_version_drift(tmp_path):
    # The version is read from the engine, not written here: a literal
    # went stale twice (12 where the engine said 14).
    root = _seed(tmp_path)
    src = (root / "native" / "sw_engine.cpp").read_text()
    now = int(re.search(r'return "starway-native-(\d+)"', src).group(1))
    _edit(root, "native/sw_engine.cpp",
          f'return "starway-native-{now}"', f'return "starway-native-{now + 1}"')
    _assert_caught(root, "contract-version", f"starway-native-{now + 1}",
                   "sw_engine.h")


def test_unmarked_multi_gib_test(tmp_path):
    root = _seed(tmp_path)
    tests = root / "tests"
    tests.mkdir()
    (tests / "test_seeded_huge.py").write_text(
        "def test_moves_4gib():\n"
        "    buf = bytearray(4 << 30)\n"
        "    assert buf\n"
    )
    _assert_caught(root, "marker-slow", "test_moves_4gib", "test_seeded_huge.py")
    # The same payload behind the marker is allowed.
    (tests / "test_seeded_huge.py").write_text(
        "import pytest\n"
        "@pytest.mark.slow\n"
        "def test_moves_4gib():\n"
        "    buf = bytearray(4 << 30)\n"
        "    assert buf\n"
    )
    assert _findings(root, "marker-slow") == []


# ----------------------------------------------------------- waiver policy


# The waiver comments below are assembled from halves so the text-based
# waiver scanner does not see live waivers inside THIS file.
_SWA = "# swcheck" + ": allow"


def test_waiver_with_justification_suppresses(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_jax.py").write_text(
        f"import jax  {_SWA}(layering-jax): exercising the waiver path\n"
    )
    assert _findings(root, "layering-jax") == []
    assert _findings(root, "bad-waiver") == []


def test_waiver_without_justification_is_a_finding(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_jax.py").write_text(
        f"import jax  {_SWA}(layering-jax)\n"
    )
    assert _findings(root, "layering-jax") == []  # replaced, not doubled
    _assert_caught(root, "bad-waiver", "no justification", "_seeded_jax.py")


def test_waiver_unknown_rule_is_a_finding(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_waiver.py").write_text(
        f"x = 1  {_SWA}(no-such-rule): why\n"
    )
    _assert_caught(root, "bad-waiver", "no-such-rule", "_seeded_waiver.py")


def test_waiver_above_line_without_justification_single_finding(tmp_path):
    # The above-the-line placement must behave like the same-line one:
    # exactly ONE bad-waiver finding, anchored at the waiver's own line.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_jax.py").write_text(
        f"{_SWA}(layering-jax)\n"
        "import jax\n"
    )
    findings = analysis.run_all(root)
    assert [(f.rule, f.line) for f in findings] == [("bad-waiver", 1)], findings


def test_bad_waiver_in_native_sources_is_audited(tmp_path):
    # Waivers are honoured in every file findings anchor to, so a broken
    # waiver in the C++ sources must be reported too.
    root = _seed(tmp_path)
    p = root / "native" / "sw_engine.cpp"
    p.write_text(p.read_text() + "\n// swcheck" + ": allow(contract-reasons): typo'd rule\n")
    _assert_caught(root, "bad-waiver", "contract-reasons", "sw_engine.cpp")


def test_handshake_key_only_in_comments_still_fails(tmp_path):
    # Deleting the negotiation code must fire even when the key survives
    # in comments/docstrings (the checker searches code literals only).
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "engine.py"
    p.write_text(p.read_text().replace('"ka"', '"kx"')
                 + '\n# the "ka" key lives only in this comment now\n')
    _assert_caught(root, "contract-handshake", '"ka"', "engine.py")
    root2 = _seed(tmp_path / "two")
    p = root2 / "native" / "sw_engine.cpp"
    p.write_text(p.read_text().replace('"ka"', '"kx"')
                 + '\n// the "ka" key lives only in this comment now\n')
    _assert_caught(root2, "contract-handshake", '"ka"', "sw_engine.cpp")


def test_unparseable_core_file_is_a_finding_in_every_pass(tmp_path):
    # No pass may skip an unparseable file vacuously -- even run standalone
    # -- and the cross-pass copies dedupe to one parse-error finding.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_syntax.py").write_text(
        "def broken(:\n")
    for passes in (["layering"], ["concurrency"], None):
        hits = [f for f in analysis.run_all(root, passes)
                if f.rule == "parse-error"]
        assert len(hits) == 1 and hits[0].file.endswith("_seeded_syntax.py"), \
            (passes, hits)


def test_parametrized_multi_gib_payload_is_caught(tmp_path):
    root = _seed(tmp_path)
    tests = root / "tests"
    tests.mkdir()
    (tests / "test_seeded_param.py").write_text(
        "import pytest\n"
        "@pytest.mark.parametrize('size', [4 << 30])\n"
        "def test_param_big(size):\n"
        "    assert bytearray(size)\n"
    )
    _assert_caught(root, "marker-slow", "test_param_big", "test_seeded_param.py")


# ---------------------------------------------------- swtrace vocabulary


def test_counter_added_to_one_engine_only(tmp_path):
    # ISSUE 4 satellite: the counter-name vocabulary is contract surface;
    # renaming (= adding/removing) a counter in the C++ array alone must
    # fire on BOTH sides of the diff.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", '"bytes_tx",', '"bytes_tx_v2",')
    _assert_caught(root, "contract-trace", "bytes_tx_v2", "sw_engine.cpp")
    _assert_caught(root, "contract-trace", "'bytes_tx'", "swtrace.py")


def test_counter_added_to_python_only(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/swtrace.py",
          '"reconnects",         # ', '"reconnects",\n    "rebalances",  # ')
    _assert_caught(root, "contract-trace", "rebalances", "swtrace.py")


def test_trace_event_value_drift(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/swtrace.py",
          'EV_SEND_POST = "send_post"', 'EV_SEND_POST = "send_posted"')
    _assert_caught(root, "contract-trace", "EV_SEND_POST", "swtrace.py")


def test_trace_event_only_in_cpp(tmp_path):
    root = _seed(tmp_path)
    p = root / "native" / "sw_engine.cpp"
    p.write_text(p.read_text().replace(
        'const char* kEvConnDown = "conn_down";',
        'const char* kEvConnDown = "conn_down";\n'
        'const char* kEvRetry = "retry";', 1))
    _assert_caught(root, "contract-trace", "kEvRetry", "sw_engine.cpp")


# ----------------------------------------------------------- hotpath pass


def test_hotpath_copy_seeded(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_copy.py").write_text(
        "def leak(view, arr):\n"
        "    a = bytes(view)\n"
        "    b = arr.tobytes()\n"
        "    c = bytes([1, 2])\n"
        "    d = bytes(16)\n"
        "    return a, b, c, d\n"
    )
    hits = _findings(root, "hotpath-copy")
    # Only the buffer copies fire; bytes([..]) / bytes(16) are allocation.
    assert {f.line for f in hits} == {2, 3}, hits
    _assert_caught(root, "hotpath-copy", "bytes(...)", "_seeded_copy.py")
    _assert_caught(root, "hotpath-copy", ".tobytes()", "_seeded_copy.py")


def test_hotpath_copy_waiver(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_copy.py").write_text(
        "def ok(view):\n"
        f"    return bytes(view)  {_SWA}(hotpath-copy): control-sized blob\n"
    )
    assert _findings(root, "hotpath-copy") == []
    assert _findings(root, "bad-waiver") == []


def test_hotpath_skips_frames_codec(tmp_path):
    # frames.py is the control-frame codec: its small bounded JSON bodies
    # are exempt by design (documented in analysis/hotpath.py).
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "frames.py"
    p.write_text(p.read_text() + "\ndef _seeded(v):\n    return bytes(v)\n")
    assert _findings(root, "hotpath-copy") == []


# ------------------------- ISSUE 5: the resilient-session contract surface
#
# The session layer grew the wire format (T_SEQ/T_ACK), a handshake key
# ("sess"), a reason literal ("session expired"), and five counters --
# every one is contract surface the checker must hold across both engines.


def test_session_frame_constant_drift(tmp_path):
    # The new frame-table rows: T_SEQ/T_ACK diverging between the engines
    # (either direction) is a finding.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py", "T_SEQ = 9", "T_SEQ = 11")
    _assert_caught(root, "contract-frames", "T_SEQ", "frames.py")
    root2 = _seed(tmp_path / "two")
    _edit(root2, "native/sw_engine.cpp",
          "constexpr uint8_t T_ACK = 10;", "constexpr uint8_t T_ACK = 12;")
    # Frame diffs anchor at the Python side of the pair (the reference
    # table), whichever engine drifted.
    _assert_caught(root2, "contract-frames", "T_ACK = 12", "frames.py")


def test_session_handshake_key_dropped(tmp_path):
    # Deleting the "sess" negotiation from either engine's code fires,
    # even when the key survives in comments/docstrings.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "engine.py"
    p.write_text(p.read_text().replace('"sess"', '"sesz"')
                 + '\n# the "sess" key lives only in this comment now\n')
    _assert_caught(root, "contract-handshake", '"sess"', "engine.py")
    root2 = _seed(tmp_path / "two")
    p = root2 / "native" / "sw_engine.cpp"
    p.write_text(p.read_text().replace('"sess"', '"sesz"')
                 + '\n// the "sess" key lives only in this comment now\n')
    _assert_caught(root2, "contract-handshake", '"sess"', "sw_engine.cpp")


def test_session_reason_reworded(tmp_path):
    # "session expired" is a stable reason keyword callers match on
    # (tests/test_session.py): rewording it fires both sub-checks --
    # keyword gone AND literal drift from the C++ kSessionExpired.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/errors.py",
          'REASON_SESSION_EXPIRED = "Session expired (resume window elapsed'
          ' or peer restarted)"',
          'REASON_SESSION_EXPIRED = "Resume window closed"')
    hits = _findings(root, "contract-reason")
    assert any("stable keyword" in f.message for f in hits), hits
    assert any("kSessionExpired" in f.message for f in hits), hits
    _assert_caught(root, "contract-reason", "REASON_SESSION_EXPIRED",
                   "errors.py")


def test_session_counter_dropped_from_cpp(tmp_path):
    # The five session counters (sessions_resumed, frames_replayed,
    # dup_frames_dropped, acks_tx/rx) are vocabulary: renaming one in the
    # C++ array alone fires on BOTH sides of the diff.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          '"sessions_resumed"', '"sessions_resumed_v2"')
    _assert_caught(root, "contract-trace", "sessions_resumed_v2",
                   "sw_engine.cpp")
    _assert_caught(root, "contract-trace", "'sessions_resumed'", "swtrace.py")


def test_session_doc_table_row_garbled(tmp_path):
    # The SEQ row of the frames.py docstring table must track T_SEQ; a
    # garbled label is "constant missing from the table", never silence.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py",
          "SEQ       next session frame's seq", "SEQX      next session frame's seq")
    hits = _findings(root, "contract-doctable")
    assert any("SEQX" in f.message for f in hits), hits
    assert any("missing from the docstring table" in f.message
               for f in hits), hits


# ---------------------- ISSUE 6: the swscope contract surface (DESIGN §15)
#
# swscope grew a handshake key ("tr"), two trace events (EV_E2E /
# EV_CLOCK), a per-conn gauge vocabulary (GAUGE_NAMES <-> kGaugeNames[]),
# and an ABI call (sw_gauges) -- each is contract surface the checker
# must hold across both engines.


def test_tr_handshake_key_dropped(tmp_path):
    # Deleting the "tr" negotiation from either engine's code fires, even
    # when the key survives in comments/docstrings.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "engine.py"
    p.write_text(p.read_text().replace('"tr"', '"tz"')
                 + '\n# the "tr" key lives only in this comment now\n')
    _assert_caught(root, "contract-handshake", '"tr"', "engine.py")
    root2 = _seed(tmp_path / "two")
    p = root2 / "native" / "sw_engine.cpp"
    p.write_text(p.read_text().replace('"tr"', '"tz"')
                 + '\n// the "tr" key lives only in this comment now\n')
    _assert_caught(root2, "contract-handshake", '"tr"', "sw_engine.cpp")


def test_gauge_dropped_from_cpp(tmp_path):
    # Renaming a gauge in the C++ array alone fires on BOTH sides of the
    # set diff (a gauge added to one engine only).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          '"journal_bytes",', '"journal_bytes_v2",')
    _assert_caught(root, "contract-trace", "journal_bytes_v2", "sw_engine.cpp")
    _assert_caught(root, "contract-trace", "'journal_bytes'", "telemetry.py")


def test_gauge_added_to_python_only(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/telemetry.py",
          '"journal_frames",', '"journal_frames",\n    "rx_backlog",')
    _assert_caught(root, "contract-trace", "rx_backlog", "telemetry.py")


def test_gauge_vocabulary_vacuity_guard(tmp_path):
    # An extractor that silently loses the vocabulary must be a finding,
    # never a vacuous pass.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/telemetry.py",
          "GAUGE_NAMES = (", "GAUGE_LABELS = (")
    _assert_caught(root, "contract-trace", "GAUGE_NAMES tuple not found",
                   "telemetry.py")


def test_e2e_event_value_drift(tmp_path):
    # The swscope events ride the existing EV_* <-> kEv* mechanical diff.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/swtrace.py",
          'EV_E2E = "e2e"', 'EV_E2E = "e2e_v2"')
    _assert_caught(root, "contract-trace", "EV_E2E", "swtrace.py")
    root2 = _seed(tmp_path / "two")
    _edit(root2, "native/sw_engine.cpp",
          'const char* kEvClock = "clock_sample";',
          'const char* kEvClock = "clock_tick";')
    _assert_caught(root2, "contract-trace", "EV_CLOCK", "swtrace.py")


def test_sw_gauges_abi_dropped(tmp_path):
    # The sw_gauges ABI row: dropping the ctypes argtypes while the
    # header still declares the function is a stale-binding finding.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "native.py"
    text = p.read_text()
    new = text.replace(
        "        lib.sw_gauges.argtypes = [\n"
        "            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int\n"
        "        ]\n", "", 1)
    assert new != text, "fixture drift: sw_gauges argtypes shape changed"
    p.write_text(new)
    _assert_caught(root, "contract-abi", "sw_gauges", "sw_engine.h")


# ---------------- ISSUE 7: swproof -- protomodel (proto-state) seededs


def test_state_annotation_value_drift(tmp_path):
    # The native arm claims a different outcome than the Python dispatch:
    # the transition-by-transition diff must name the disagreeing pair.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, ACK, estab)",
          "// swcheck: state(estab, ACK, down)")
    hits = _findings(root, "proto-state")
    assert any("(estab, ACK)" in f.message and "disagree" in f.message
               for f in hits), hits
    _assert_caught(root, "proto-state", "(estab, ACK)", "conn.py")


def test_state_annotation_missing(tmp_path):
    # Deleting a dispatch annotation = the native engine no longer claims
    # the arm: anchored at the Python side of the pair.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, BYE, estab|expired)\n", "")
    _assert_caught(root, "proto-state", "(estab, BYE)", "conn.py")


def test_state_python_arm_drift(tmp_path):
    # Renaming a Python dispatch arm fires BOTH ways: the new arm has no
    # annotation, the old annotation has no counterpart.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "elif ftype == frames.T_BYE:", "elif ftype == frames.T_BYEX:")
    hits = _findings(root, "proto-state")
    assert any("(estab, BYEX)" in f.message for f in hits), hits
    assert any("(estab, BYE)" in f.message and "no counterpart" in f.message
               for f in hits), hits
    _assert_caught(root, "proto-state", "(estab, BYE)", "sw_engine.cpp")


def test_state_extraction_vacuity(tmp_path):
    # Stripping every annotation must be a finding, never a vacuous pass
    # (empty extraction is a finding -- the acceptance bar).
    root = _seed(tmp_path)
    p = root / "native" / "sw_engine.cpp"
    p.write_text(re.sub(r"// swcheck: state\([^)]*\)\n", "", p.read_text()))
    _assert_caught(root, "proto-state", "no `swcheck: state(...)` annotations",
                   "sw_engine.cpp")


def test_state_unknown_token(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, PING, estab)",
          "// swcheck: state(estab, PINGG, estab)")
    hits = _findings(root, "proto-state")
    assert any("unknown token" in f.message and "PINGG" in f.message
               for f in hits), hits


def test_state_waiver(tmp_path):
    # proto-state findings ride the standard waiver policy at their
    # anchor line (here: the Python arm the native side stopped claiming).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, BYE, estab|expired)\n", "")
    _edit(root, "starway_tpu/core/conn.py",
          "            elif ftype == frames.T_BYE:",
          f"            {_SWA}(proto-state): exercising the waiver path\n"
          "            elif ftype == frames.T_BYE:")
    assert _findings(root, "proto-state") == []
    assert _findings(root, "bad-waiver") == []


# ------------------- ISSUE 7: swproof -- explore (proto-explore) model


def test_explore_head_clean_and_schedule_floor():
    # The faithful §14 model must exhaust clean, and the enumeration must
    # cover >= 1k distinct fault schedules (the acceptance floor).
    from starway_tpu.analysis import explore

    result = explore.check(None)
    assert result["violations"] == [], result["violations"]
    assert result["schedules"] >= 1000, result["schedules"]
    assert result["states"] > 100


def test_explore_every_invariant_fires_under_its_mutation():
    # Every invariant is backed by a seeded model mutation that makes it
    # fire -- otherwise the checker could never see the failure it
    # claims to rule out.
    from starway_tpu.analysis import explore

    assert set(explore.MUTATIONS.values()) == set(explore.INVARIANTS)
    for mutation, invariant in explore.MUTATIONS.items():
        result = explore.check(mutation)
        fired = {v[0] for v in result["violations"]}
        assert invariant in fired, (mutation, invariant, fired)


def test_explore_refuses_vacuity_when_machine_drifts(tmp_path):
    # If extraction loses the session transitions the model abstracts,
    # explore must flag the desync instead of checking a machine the
    # code no longer implements.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "elif ftype == frames.T_SEQ:", "elif ftype == frames.T_SEQX:")
    _assert_caught(root, "proto-explore", "no longer extracted", "session.py")


def test_explore_waiver(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "elif ftype == frames.T_SEQ:", "elif ftype == frames.T_SEQX:")
    p = root / "starway_tpu" / "core" / "session.py"
    p.write_text(f"{_SWA}(proto-explore): exercising the waiver path\n"
                 + p.read_text())
    assert _findings(root, "proto-explore") == []


# ------------- ISSUE 7: swproof -- concurrency v2 interprocedural rules


def test_reachable_blocking_seeded(tmp_path):
    # The PR-6 sampler bug class: lexically clean under the lock, but a
    # helper one call down blocks.  The direct lint cannot see it; the
    # interprocedural pass must, anchored at the under-lock call site.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_reach.py").write_text(
        "import time\n"
        "class Sampler:\n"
        "    def _grab_sample(self):\n"
        "        time.sleep(0.5)\n"
        "    def tick(self):\n"
        "        with self.sample_lock:\n"
        "            self._grab_sample()\n"
    )
    hits = _findings(root, "reachable-blocking")
    assert any(f.line == 7 for f in hits), hits
    _assert_caught(root, "reachable-blocking", "time.sleep",
                   "_seeded_reach.py")
    # The helper's own direct finding still fires under the v1 rule.
    _assert_caught(root, "blocking-call", "time.sleep", "_seeded_reach.py")


def test_reachable_blocking_waiver(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_reach.py").write_text(
        "import time\n"
        "class Sampler:\n"
        "    def _grab_sample(self):\n"
        f"        time.sleep(0.5)  {_SWA}(blocking-call): seeded fixture\n"
        "    def tick(self):\n"
        "        with self.sample_lock:\n"
        f"            self._grab_sample()  {_SWA}(reachable-blocking): seeded fixture\n"
    )
    assert _findings(root, "reachable-blocking") == []
    assert _findings(root, "blocking-call") == []
    assert _findings(root, "bad-waiver") == []


def test_reachable_blocking_through_mutual_recursion(tmp_path):
    # Regression (review round): a cycle member probed first must not
    # cache a false 'unreachable' that suppresses a later query through
    # the same cycle -- the answer must not depend on query order.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_cycle.py").write_text(
        "import time\n"
        "class S:\n"
        "    def a(self, n):\n"
        "        self.b(n)\n"
        "        self.c(n)\n"
        "    def b(self, n):\n"
        "        self.a(n)\n"
        "    def c(self, n):\n"
        "        time.sleep(0.1)\n"
        "    def early(self):\n"
        "        with self.lock:\n"
        "            self.a(1)\n"
        "    def late(self):\n"
        "        with self.lock:\n"
        "            self.b(1)\n"
    )
    hits = [f for f in _findings(root, "reachable-blocking")
            if f.file.endswith("_seeded_cycle.py")]
    # BOTH under-lock call sites reach time.sleep (a -> c, b -> a -> c).
    assert {f.line for f in hits} == {12, 15}, hits


def test_duck_attr_while_narrowing(tmp_path):
    # Regression (review round): a while test narrows exactly like an if
    # test -- `while isinstance(item, TxData):` must not flag the body.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_while.py").write_text(
        "def pump(conn):\n"
        "    item = conn.tx[0]\n"
        "    while isinstance(item, TxData) and not item.local_done:\n"
        "        item._maybe_local_complete([])\n"
    )
    assert [f for f in _findings(root, "duck-attr")
            if f.file.endswith("_seeded_while.py")] == []


def test_reachable_callback_under_lock_seeded(tmp_path):
    # A callback invoked one call below the lock: v1's lexical lint is
    # blind to it, v2 follows the call graph (deferred lambdas stay the
    # allowed pattern and must NOT fire).
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_cbreach.py").write_text(
        "class W:\n"
        "    def _notify_user(self, done):\n"
        "        done()\n"
        "    def bad(self, done):\n"
        "        with self.lock:\n"
        "            self._notify_user(done)\n"
        "    def good(self, done, fires):\n"
        "        with self.lock:\n"
        "            fires.append(lambda: self._notify_user(done))\n"
    )
    hits = [f for f in _findings(root, "callback-under-lock")
            if f.file.endswith("_seeded_cbreach.py")]
    assert {f.line for f in hits} == {6}, hits
    assert any("reaches user callback" in f.message for f in hits), hits


def test_lock_order_cycle_seeded(tmp_path):
    # Two functions taking the same two locks in opposite orders: the
    # classic deadlock shape the lock-order graph must close on.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_order.py").write_text(
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "def one():\n"
        "    with a_lock:\n"
        "        with b_lock:\n"
        "            pass\n"
        "def two():\n"
        "    with b_lock:\n"
        "        with a_lock:\n"
        "            pass\n"
    )
    _assert_caught(root, "lock-order", "cycle", "_seeded_order.py")
    hits = _findings(root, "lock-order")
    assert any("a_lock" in f.message and "b_lock" in f.message
               for f in hits), hits


def test_lock_order_waiver(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_order.py").write_text(
        "import threading\n"
        "a_lock = threading.Lock()\n"
        "b_lock = threading.Lock()\n"
        "def one():\n"
        "    with a_lock:\n"
        "        with b_lock:\n"
        "            pass\n"
        "def two():\n"
        "    with b_lock:\n"
        f"        {_SWA}(lock-order): seeded fixture, never runs\n"
        "        with a_lock:\n"
        "            pass\n"
    )
    # The anchor is the edge that closes the cycle; with both closing
    # edges waiver-covered the cycle report is suppressed.
    hits = _findings(root, "lock-order")
    if hits:  # cycle may anchor at the OTHER closing edge -- cover it too
        (root / "starway_tpu" / "core" / "_seeded_order.py").write_text(
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def one():\n"
            "    with a_lock:\n"
            f"        {_SWA}(lock-order): seeded fixture, never runs\n"
            "        with b_lock:\n"
            "            pass\n"
            "def two():\n"
            "    with b_lock:\n"
            f"        {_SWA}(lock-order): seeded fixture, never runs\n"
            "        with a_lock:\n"
            "            pass\n"
        )
        hits = _findings(root, "lock-order")
    assert hits == [], hits
    assert _findings(root, "bad-waiver") == []


def test_duck_attr_pr6_regression(tmp_path):
    # THE seeded regression for the duck-type checker: the PR-6 crash was
    # an unguarded `item.counted` read reaching a TxCtl (whose __slots__
    # lack `counted`) on the engine thread.  Re-introduce exactly that
    # shape and assert swproof flags it at the right line.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_duck.py").write_text(
        "def pump(conn, fires):\n"
        "    for item in conn.tx:\n"
        "        if item.counted:\n"
        "            item.e2e_ord = 1\n"
    )
    hits = [f for f in _findings(root, "duck-attr")
            if f.file.endswith("_seeded_duck.py")]
    assert {f.line for f in hits} == {3, 4}, hits
    assert any("counted" in f.message and "TxCtl" in f.message
               for f in hits), hits


def test_duck_attr_guarded_reads_are_clean(tmp_path):
    # The two sanctioned shapes -- isinstance narrowing and getattr with
    # a default (the actual PR-6 fix) -- must stay clean.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_duck.py").write_text(
        "def pump(conn):\n"
        "    for item in conn.tx:\n"
        "        if not isinstance(item, TxCtl) and not item.counted:\n"
        "            item.counted = True\n"
        "        if getattr(item, 'switch_after', False):\n"
        "            pass\n"
        "        if isinstance(item, TxData):\n"
        "            item._maybe_local_complete([])\n"
        "        item.advance(1, [])\n"
    )
    assert [f for f in _findings(root, "duck-attr")
            if f.file.endswith("_seeded_duck.py")] == []


def test_duck_attr_waiver(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_duck.py").write_text(
        "def pump(conn):\n"
        "    for item in conn.tx:\n"
        f"        return item.counted  {_SWA}(duck-attr): seeded fixture\n"
    )
    assert _findings(root, "duck-attr") == []
    assert _findings(root, "bad-waiver") == []


# --------------- ISSUE 7: lint-surface coverage audit (lint-coverage)


def test_coverage_new_module_outside_surface(tmp_path):
    # A new top-level runtime module that grows a policed primitive must
    # join the lint surface (the metrics.py gap class).
    root = _seed(tmp_path)
    (root / "starway_tpu" / "_seeded_tail.py").write_text(
        "import time\n"
        "def follow():\n"
        "    time.sleep(0.2)\n"
    )
    _assert_caught(root, "lint-coverage", "outside the swcheck lint surface",
                   "_seeded_tail.py")


def test_coverage_declared_surface_file_missing(tmp_path):
    # A surface file deleted/renamed without updating LINT_EXTRA_FILES is
    # exactly the "pass list post-dates the tree" drift.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "metrics.py").unlink()
    hits = _findings(root, "lint-coverage")
    assert any("does not exist" in f.message for f in hits), hits


def test_coverage_waiver(tmp_path):
    root = _seed(tmp_path)
    (root / "starway_tpu" / "_seeded_tail.py").write_text(
        "import time\n"
        "def follow():\n"
        f"    time.sleep(0.2)  {_SWA}(lint-coverage): seeded fixture\n"
    )
    assert _findings(root, "lint-coverage") == []
    assert _findings(root, "bad-waiver") == []


# ------- ISSUE 7: the newly covered surface files actually get linted


def test_session_py_violation_is_caught(tmp_path):
    # core/session.py post-dated the v1 pass lists; prove the surface
    # audit holds by seeding a violation INTO it and watching it fire.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "session.py"
    p.write_text(p.read_text()
                 + "\ndef _seeded_spin():\n    time.sleep(0.5)\n")
    _assert_caught(root, "blocking-call", "time.sleep", "session.py")


def test_telemetry_py_violation_is_caught(tmp_path):
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "telemetry.py"
    p.write_text(p.read_text()
                 + "\ndef _seeded_copy(view):\n    return bytes(view)\n")
    _assert_caught(root, "hotpath-copy", "bytes(...)", "telemetry.py")


def test_metrics_py_violation_is_caught(tmp_path):
    # metrics.py is the file the coverage audit pulled INTO the surface:
    # both the concurrency and hotpath passes must see it now.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "metrics.py"
    p.write_text(p.read_text()
                 + "\ndef _seeded_copy(view):\n    return bytes(view)\n"
                 "\ndef _seeded_spin():\n    time.sleep(0.5)\n")
    _assert_caught(root, "hotpath-copy", "bytes(...)", "metrics.py")
    _assert_caught(root, "blocking-call", "time.sleep", "metrics.py")


# ----------------------------------------------- gate budget + CLI surface


def test_full_gate_under_budget():
    # All passes -- explore's exhaustive enumeration included -- must fit
    # the 60 s budget on the 1-core box (ISSUE 7 satellite; the parse
    # cache is what keeps repeated per-pass reads out of the bill).
    import time as _time

    t0 = _time.perf_counter()
    findings = analysis.run_all(REPO)
    elapsed = _time.perf_counter() - t0
    assert findings == [], "\n".join(f.render() for f in findings)
    assert elapsed < 60.0, f"gate took {elapsed:.1f}s (budget 60s)"


def test_cli_json_and_timings(tmp_path, capsys):
    import json as _json

    from starway_tpu.analysis.__main__ import main

    assert main(["--root", str(REPO), "--json", "--timings"]) == 0
    out = capsys.readouterr()
    doc = _json.loads(out.out)
    assert doc["ok"] is True and doc["findings"] == []
    assert set(doc["timings_s"]) == set(analysis.PASSES)
    assert "pass" in out.err  # --timings table on stderr
    # Findings shape carries file/line/rule/message for the CI matcher.
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_jax.py").write_text("import jax\n")
    assert main(["--root", str(root), "--json"]) == 1
    doc = _json.loads(capsys.readouterr().out)
    assert doc["ok"] is False
    assert any(f["rule"] == "layering-jax" and f["line"] == 1
               for f in doc["findings"])


def test_cli_exit_codes(tmp_path):
    from starway_tpu.analysis.__main__ import main

    assert main(["--root", str(REPO)]) == 0
    assert main(["--root", str(REPO), "--rules"]) == 0
    root = _seed(tmp_path)
    (root / "starway_tpu" / "core" / "_seeded_jax.py").write_text("import jax\n")
    assert main(["--root", str(root)]) == 1
    assert main(["--root", str(root), "contract"]) == 0  # pass selection
    with pytest.raises(SystemExit):
        main(["--root", str(root), "nonsense-pass"])


# -------------------------------------------- ISSUE 8: stripe contract


def test_bumped_sdata_frame_constant(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py", "T_SDATA = 12", "T_SDATA = 14")
    _assert_caught(root, "contract-frames", "T_SDATA", "frames.py")


def test_changed_sdata_subheader_layout(tmp_path):
    # The 24-byte stripe sub-header is wire format: shrinking the Python
    # struct must diff against the native SDATA_SUB_SIZE constexpr.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py",
          'SDATA_SUB = struct.Struct("<QQQ")',
          'SDATA_SUB = struct.Struct("<QQ")')
    _assert_caught(root, "contract-header", "SDATA_SUB", "frames.py")


def test_rails_handshake_key_dropped(tmp_path):
    # Deleting the rails negotiation from one engine only must fire, even
    # with the key surviving in comments (code-literal search only).
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "engine.py"
    p.write_text(p.read_text().replace('"rails"', '"railx"')
                 + '\n# the "rails" key lives only in this comment now\n')
    _assert_caught(root, "contract-handshake", '"rails"', "engine.py")
    root2 = _seed(tmp_path / "two")
    p2 = root2 / "native" / "sw_engine.cpp"
    p2.write_text(p2.read_text().replace('"rail_of"', '"rail_xx"'))
    _assert_caught(root2, "contract-handshake", '"rail_of"', "sw_engine.cpp")


def test_stripe_counter_dropped_from_native(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          '"stripe_chunks_tx",  "stripe_chunks_rx",',
          '"stripe_chunks_tx_v2",  "stripe_chunks_rx",')
    _assert_caught(root, "contract-trace", "stripe_chunks_tx_v2",
                   "sw_engine.cpp")
    _assert_caught(root, "contract-trace", "'stripe_chunks_tx'",
                   "swtrace.py")


def test_stripe_gauge_dropped_from_python(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/telemetry.py",
          '"stripe_pending",', '')
    _assert_caught(root, "contract-trace", "stripe_pending",
                   "sw_engine.cpp")


def test_sdata_dispatch_annotation_drift(tmp_path):
    # Re-routing the native SDATA arm's annotated outcome must diff
    # against the Python engine's extracted transition (proto-state).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, SDATA, estab|down)",
          "// swcheck: state(estab, SDATA, estab)")
    _assert_caught(root, "proto-state", "SDATA", "conn.py")


# ------------- ISSUE 9: the §18 flow-control contract surface


def test_credit_frame_constant_drift(tmp_path):
    # The new frame rows: T_CREDIT/T_RTS/T_CTS diverging between the
    # engines (either direction) is a finding.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py", "T_CREDIT = 14", "T_CREDIT = 17")
    _assert_caught(root, "contract-frames", "T_CREDIT", "frames.py")
    root2 = _seed(tmp_path / "two")
    _edit(root2, "native/sw_engine.cpp",
          "constexpr uint8_t T_RTS = 15;", "constexpr uint8_t T_RTS = 18;")
    _assert_caught(root2, "contract-frames", "T_RTS = 18", "frames.py")


def test_fc_handshake_key_dropped(tmp_path):
    # Deleting the "fc" negotiation from either engine's code fires,
    # even when the key survives in comments/docstrings.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "engine.py"
    p.write_text(p.read_text().replace('"fc"', '"fz"')
                 + '\n# the "fc" key lives only in this comment now\n')
    _assert_caught(root, "contract-handshake", '"fc"', "engine.py")
    root2 = _seed(tmp_path / "two")
    p2 = root2 / "native" / "sw_engine.cpp"
    # The checker matches the bare `"fc"` code literal (the json_field
    # reads); the escaped \"fc\" string-building fragments never match
    # it, so renaming the reads alone must fire.
    p2.write_text(p2.read_text().replace('"fc"', '"fz"')
                  + '\n// the "fc" key lives only in this comment now\n')
    _assert_caught(root2, "contract-handshake", '"fc"', "sw_engine.cpp")


def test_fc_counter_dropped_from_native(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          '"sends_parked",      "sheds",', '"sends_parked_v2",      "sheds",')
    _assert_caught(root, "contract-trace", "sends_parked_v2", "sw_engine.cpp")
    _assert_caught(root, "contract-trace", "'sends_parked'", "swtrace.py")


def test_fc_gauge_dropped_from_python(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/telemetry.py", '"credits_avail",', '')
    _assert_caught(root, "contract-trace", "credits_avail", "sw_engine.cpp")


def test_credit_doc_table_row_garbled(tmp_path):
    # The CREDIT row of the frames.py docstring table must track
    # T_CREDIT; a garbled label is "constant missing from the table".
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py",
          "CREDIT    granted window bytes", "CREDITX   granted window bytes")
    hits = _findings(root, "contract-doctable")
    assert any("CREDITX" in f.message for f in hits), hits
    assert any("missing from the docstring table" in f.message
               for f in hits), hits


def test_credit_state_annotation_drift(tmp_path):
    # Re-routing the native CREDIT arm's annotated outcome must diff
    # against the Python engine's extracted transition (the ISSUE-9
    # `state(estab, CREDIT, estab)` requirement).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, CREDIT, estab)",
          "// swcheck: state(estab, CREDIT, estab|down)")
    _assert_caught(root, "proto-state", "CREDIT", "conn.py")


def test_explore_credit_conservation_mutation():
    # The §18 credit-conservation invariant is backed by its seeded
    # mutation: a resume carrying stale credits across the incarnation
    # must make exactly it fire (the kill swallowed in-flight grants).
    from starway_tpu.analysis import explore

    clean = explore.check(None)
    assert not any(v[0] == "credit-conservation"
                   for v in clean["violations"]), clean["violations"]
    leaked = explore.check("credit-leak")
    fired = {v[0] for v in leaked["violations"]}
    assert "credit-conservation" in fired, fired


# ------------------- ISSUE 11: the §19 integrity plane contract surface
#
# The integrity plane grew two frame types (T_CSUM/T_SNACK), a handshake
# key ("csum"), a stable poison reason ("corrupt"), an sm slot-record
# trailer layout (REC_HDR <-> SM_REC_HDR), two counters, a gauge, an ABI
# export (sw_crc32c), and new dispatch transitions -- every row below
# seeds one violation and pins that the matching rule fires.


def test_csum_frame_constant_drift(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py", "T_SNACK = 18", "T_SNACK = 19")
    _assert_caught(root, "contract-frames", "T_SNACK", "frames.py")
    root2 = _seed(tmp_path / "two")
    _edit(root2, "native/sw_engine.cpp",
          "constexpr uint8_t T_CSUM = 17;", "constexpr uint8_t T_CSUM = 19;")
    _assert_caught(root2, "contract-frames", "T_CSUM = 19", "frames.py")


def test_csum_handshake_key_dropped(tmp_path):
    # Deleting the "csum" negotiation from either engine's code fires,
    # even when the key survives in comments/docstrings.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "engine.py"
    p.write_text(p.read_text().replace('"csum"', '"csux"')
                 + '\n# the "csum" key lives only in this comment now\n')
    _assert_caught(root, "contract-handshake", '"csum"', "engine.py")
    root2 = _seed(tmp_path / "two")
    p = root2 / "native" / "sw_engine.cpp"
    p.write_text(p.read_text().replace('"csum"', '"csux"')
                 + '\n// the "csum" key lives only in this comment now\n')
    _assert_caught(root2, "contract-handshake", '"csum"', "sw_engine.cpp")


def test_corrupt_reason_reworded(tmp_path):
    # "corrupt" is the stable poison keyword callers match on
    # (tests/test_integrity.py): rewording fires both sub-checks.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/errors.py",
          'REASON_CORRUPT = "Data integrity violation (corrupt frame'
          ' detected)"',
          'REASON_CORRUPT = "Checksum mismatch"')
    hits = _findings(root, "contract-reason")
    assert any("stable keyword" in f.message for f in hits), hits
    assert any("kCorrupt" in f.message for f in hits), hits
    _assert_caught(root, "contract-reason", "REASON_CORRUPT", "errors.py")


def test_sm_slot_trailer_layout_drift(tmp_path):
    # The slot-record header size is shared segment framing: the engines
    # disagreeing on it would silently interleave garbage.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "constexpr size_t SM_REC_HDR = 8;", "constexpr size_t SM_REC_HDR = 16;")
    _assert_caught(root, "contract-shm", "SM_REC_HDR", "shmring.py")


def test_csum_counter_dropped_from_cpp(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", '"csum_fail"', '"csum_fail_v2"')
    _assert_caught(root, "contract-trace", "csum_fail_v2", "sw_engine.cpp")
    _assert_caught(root, "contract-trace", "'csum_fail'", "swtrace.py")


def test_retx_gauge_dropped_from_cpp(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", '"retx_pending",', "")
    _assert_caught(root, "contract-trace", "retx_pending", "telemetry.py")


def test_csum_doc_table_row_garbled(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py",
          "SNACK     corrupt chunk's msg id", "SNACKX    corrupt chunk's msg id")
    hits = _findings(root, "contract-doctable")
    assert any("SNACKX" in f.message for f in hits), hits
    assert any("missing from the docstring table" in f.message
               for f in hits), hits


def test_csum_state_annotation_drift(tmp_path):
    # The CSUM gate can tear the conn down (nested/missing checksum):
    # the native annotation claiming estab-only must diff against the
    # Python extraction.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, CSUM, estab|down)",
          "// swcheck: state(estab, CSUM, estab)")
    _assert_caught(root, "proto-state", "CSUM", "conn.py")


def test_snack_state_annotation_missing(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "// swcheck: state(estab, SNACK, estab)\n", "")
    _assert_caught(root, "proto-state", "(estab, SNACK)", "conn.py")


def test_sw_crc32c_abi_dropped(tmp_path):
    # Removing the export from the header while the ctypes binding stays
    # is a stale-binding finding (and vice versa would be a missing
    # argtypes finding) -- the §19 checksum must stay one function.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.h",
          "uint32_t sw_crc32c(const void* p, uint64_t n, uint32_t seed);", "")
    _assert_caught(root, "contract-abi", "sw_crc32c", "native.py")


# ------------ ISSUE 14: swcompose -- compose (proto-compose) product model


def test_swcompose_rules_registered():
    # Satellite: the three new finding codes are waiver targets
    # (--rules) and render as problem-matcher rows like every pass.
    for rule in ("proto-compose", "wire-diff", "taint-integrity"):
        assert rule in analysis.RULES, rule


def test_compose_head_clean_and_schedule_floor():
    # The faithful composed model (sessions x striping x fc x integrity)
    # must exhaust clean, over a product space comfortably past the
    # single-plane explore floor.
    from starway_tpu.analysis import compose

    result = compose.check(None)
    assert result["violations"] == [], result["violations"]
    assert result["schedules"] >= 2000, result["schedules"]
    assert result["states"] > 1000, result["states"]


def test_compose_every_invariant_fires_under_its_mutation():
    # Repo convention: every invariant is backed by a seeded model
    # mutation that makes the checker fail -- otherwise it could never
    # see the failure class it claims to rule out.
    from starway_tpu.analysis import compose

    assert set(compose.MUTATIONS.values()) == set(compose.INVARIANTS)
    for mutation, invariant in compose.MUTATIONS.items():
        result = compose.check(mutation)
        fired = {v[0] for v in result["violations"]}
        assert invariant in fired, (mutation, invariant, fired)


def test_compose_unknown_mutation_rejected():
    from starway_tpu.analysis import compose

    with pytest.raises(ValueError):
        compose.check("no-such-mutation")


def test_compose_refuses_vacuity_when_machine_drifts(tmp_path):
    # If extraction loses the striping dispatch arm the product model
    # abstracts, compose must flag the desync instead of verifying
    # planes the code no longer implements.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "elif ftype == frames.T_SDATA:", "elif ftype == frames.T_SDATAX:")
    _assert_caught(root, "proto-compose", "no longer extracted", "lane.py")


def test_compose_waiver(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "elif ftype == frames.T_SDATA:", "elif ftype == frames.T_SDATAX:")
    p = root / "starway_tpu" / "core" / "lane.py"
    p.write_text(f"{_SWA}(proto-compose): exercising the waiver path\n"
                 + p.read_text())
    assert _findings(root, "proto-compose") == []


# ------------- ISSUE 14: swcompose -- wirefuzz (wire-diff) differential


def test_wirefuzz_head_replays_corpus_clean_with_native():
    # The acceptance bar: the checked-in corpus (>= the 100-case floor)
    # plus the quick-mode generator replays with zero divergence across
    # the oracle, frames.decode_stream/decode_sm_records, AND the native
    # sw_wire_decode export (the built artifact must be present here).
    from starway_tpu.analysis import wirefuzz

    out: list = []
    got = wirefuzz._extract_tables(REPO, out)
    assert got is not None and out == [], [f.render() for f in out]
    counts = wirefuzz.fuzz(REPO, got[0], out,
                           seeds_per_mode=wirefuzz.QUICK_SEEDS)
    assert out == [], [f.render() for f in out]
    assert counts["native"], "native sw_wire_decode export not loaded"
    assert counts["divergences"] == 0
    assert counts["cases"] >= (wirefuzz.CORPUS_FLOOR
                               + 3 * wirefuzz.QUICK_SEEDS), counts


def test_wirefuzz_fixed_divergence_seed_pinned():
    # The zero-length ctl body was a REAL cross-engine divergence (C++
    # silently dropped the frame; the Python parser issued a 0-byte read
    # -- conn death on TCP, a permanent stall on sm rings).  Both
    # engines now reject it identically; the corpus pins the bytes.
    from starway_tpu.analysis import wirefuzz
    from starway_tpu.core import frames

    zero_ctl = bytes.fromhex("0100000000000000000000000000000000")
    want = "reject(zero control body) n=0 []"
    assert frames.decode_stream(zero_ctl) == want
    lib = wirefuzz._load_native(REPO)
    assert lib is not None, "native decode harness missing"
    assert wirefuzz._native_decode(lib, zero_ctl, "stream") == want
    corpus = (REPO / "starway_tpu" / "analysis"
              / "wirefuzz_corpus.txt").read_text()
    assert zero_ctl.hex() in corpus, "divergent seed not pinned in corpus"


def test_wirefuzz_python_decoder_divergence_seeded(tmp_path):
    # Mutate the reference decoder's ctl-body rule: the oracle (derived
    # from the contract tables, not the decoder) catches the divergence
    # on the pinned corpus bytes.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py",
          '            if b == 0:\n'
          '                return done("reject(zero control body)")',
          '            if b == 0 and False:\n'
          '                return done("reject(zero control body)")')
    _assert_caught(root, "wire-diff", "Python decoder diverges", "frames.py")


def test_wirefuzz_smrec_divergence_seeded(tmp_path):
    # Mutate the slot-record decoder's seqno seed: every valid record
    # now rejects, diverging from the oracle in mode smrec.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/shmring.py",
          "frames.crc32c(_SEQ8.pack(seq))",
          "frames.crc32c(_SEQ8.pack(seq + 1))")
    _assert_caught(root, "wire-diff", "diverges", "shmring.py")


def test_wirefuzz_native_table_drift_seeded(tmp_path):
    # The static leg: kCsumExempt[] losing a member diffs against
    # frames.CSUM_EXEMPT without running a single byte.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "constexpr uint8_t kCsumExempt[] = {T_HELLO, T_HELLO_ACK, T_SEQ};",
          "constexpr uint8_t kCsumExempt[] = {T_HELLO, T_HELLO_ACK};")
    _assert_caught(root, "wire-diff", "kCsumExempt", "frames.py")


def test_wirefuzz_ctl_bound_drift_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "constexpr uint64_t CTL_MAX = 1ull << 20;",
          "constexpr uint64_t CTL_MAX = 1ull << 21;")
    _assert_caught(root, "wire-diff", "ctl-body bound", "frames.py")


def test_wirefuzz_smrec_ring_bound_drift_seeded(tmp_path):
    # The smrec record-length bound is pinned statically like CTL_MAX:
    # the oracle follows the tree's shmring.DEFAULT_RING, the native
    # harness hardcodes its twin, and a drift is a finding even with no
    # built artifact to fuzz (the corpus boundary cases fire it
    # dynamically too).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "const uint64_t ring_size = 1ull << 24;",
          "const uint64_t ring_size = 1ull << 25;")
    _assert_caught(root, "wire-diff", "record-length bound", "shmring.py")


def test_wirefuzz_private_parser_table_seeded(tmp_path):
    # The live parser growing a private decode table (instead of
    # aliasing frames.CSUM_EXEMPT) is the drift the fuzzer cannot see
    # dynamically -- the alias check catches it statically.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "_CSUM_EXEMPT = frames.CSUM_EXEMPT",
          "_CSUM_EXEMPT = frozenset((frames.T_HELLO, frames.T_HELLO_ACK,"
          " frames.T_SEQ))")
    _assert_caught(root, "wire-diff", "no longer aliases", "conn.py")


def test_wirefuzz_corpus_floor_and_malformed_lines(tmp_path):
    # A truncated or garbled corpus is itself a finding, never a silent
    # skip (the seeded tree's own corpus shadows the checked-in one).
    root = _seed(tmp_path)
    adir = root / "starway_tpu" / "analysis"
    adir.mkdir(parents=True)
    (adir / "wirefuzz_corpus.txt").write_text(
        "# truncated corpus\n"
        "seed stream 1\n"
        "bogus stream 2\n"
        "hex stream zz\n")
    _assert_caught(root, "wire-diff", "below the", "wirefuzz_corpus.txt")
    _assert_caught(root, "wire-diff", "malformed corpus",
                   "wirefuzz_corpus.txt")


def test_wirefuzz_waiver(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "constexpr uint8_t kCsumExempt[] = {T_HELLO, T_HELLO_ACK, T_SEQ};",
          "constexpr uint8_t kCsumExempt[] = {T_HELLO, T_HELLO_ACK};")
    _edit(root, "starway_tpu/core/frames.py",
          "CSUM_EXEMPT = frozenset((T_HELLO, T_HELLO_ACK, T_SEQ))",
          f"{_SWA}(wire-diff): exercising the waiver path\n"
          "CSUM_EXEMPT = frozenset((T_HELLO, T_HELLO_ACK, T_SEQ))")
    assert _findings(root, "wire-diff") == []
    assert _findings(root, "bad-waiver") == []


@pytest.mark.slow
def test_wirefuzz_long_soak():
    # The nightly CI leg's in-repo twin: a deep generator run over all
    # three modes with zero divergence (quick mode covers the gate).
    from starway_tpu.analysis import wirefuzz

    out: list = []
    got = wirefuzz._extract_tables(REPO, out)
    assert got is not None and out == [], [f.render() for f in out]
    counts = wirefuzz.fuzz(REPO, got[0], out, seeds_per_mode=20000)
    assert out == [], [f.render() for f in out]
    assert counts["cases"] >= 60000, counts


# ------------- ISSUE 14: swcompose -- taint (taint-integrity) lint


def test_taint_dropped_accumulation_seeded(tmp_path):
    # Remove the guarded CRC accumulation on the eager-body read: the
    # eventual verify goes blind to those bytes.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "                if self._csum_pend is not None:\n"
          "                    self._csum_accum = frames.crc32c(target[:n],\n"
          "                                                     self._csum_accum)\n"
          "                m.received += n",
          "                m.received += n")
    _assert_caught(root, "taint-integrity", "CRC accumulator", "conn.py")


def test_taint_softened_gate_seeded(tmp_path):
    # Soften the pre-completion mismatch arm from poison to a counter
    # bump: corrupt bytes would complete the receive.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          '                        if self._csum_accum != pend[0]:\n'
          '                            self._corrupt(fires, "payload checksum (DATA)")\n'
          '                            return',
          '                        if self._csum_accum != pend[0]:\n'
          '                            self._ctr.csum_fail += 1')
    _assert_caught(root, "taint-integrity", "does not abort", "conn.py")


def test_taint_sm_poison_dropped_seeded(tmp_path):
    # The SmCorrupt handler must surface the stable "corrupt" poison;
    # dropping the poison_reason assignment degrades it to a generic
    # conn break (or worse).
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "                self.poison_reason = REASON_CORRUPT\n"
          "                if self.sess is None or self.sess.expired:",
          "                if self.sess is None or self.sess.expired:")
    _assert_caught(root, "taint-integrity", "SmCorrupt", "conn.py")


def test_taint_shmring_raise_dropped_seeded(tmp_path):
    # Ring.read_into silently tolerating a checksum mismatch means torn
    # ring bytes parse as frames.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/shmring.py",
          'raise SmCorrupt("sm slot record checksum mismatch "',
          'raise OSError("sm slot record checksum mismatch "')
    _edit(root, "starway_tpu/core/shmring.py",
          'raise SmCorrupt("sm slot record header corrupt "',
          'raise OSError("sm slot record header corrupt "')
    _assert_caught(root, "taint-integrity", "read_into", "shmring.py")


def test_taint_cpp_sm_poison_dropped_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          'conn_corrupt(c, "sm slot record", fires);',
          'bump(counters.csum_fail);')
    _assert_caught(root, "taint-integrity", "sm slot record",
                   "sw_engine.cpp")


def test_taint_cpp_dropped_accumulation_seeded(tmp_path):
    # Remove the striped-chunk payload accumulation in the native rx
    # arm: the chunk-level verify goes blind (first occurrence of this
    # exact statement is the rx_stripe arm).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "c->csum_accum = crc32c(target, (size_t)r, c->csum_accum);",
          ";")
    _assert_caught(root, "taint-integrity", "CRC accumulator",
                   "sw_engine.cpp")


def test_taint_refuses_vacuity_when_pump_renamed(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "def _pump_frames(self, fires: list) -> None:",
          "def _pump_frames_gone(self, fires: list) -> None:")
    _assert_caught(root, "taint-integrity", "_pump_frames not found",
                   "conn.py")


def test_taint_waiver(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "                self.poison_reason = REASON_CORRUPT\n"
          "                if self.sess is None or self.sess.expired:",
          "                if self.sess is None or self.sess.expired:")
    _edit(root, "starway_tpu/core/conn.py",
          "    def _rx_read(self, target) -> int:",
          f"    {_SWA}(taint-integrity): exercising the waiver path\n"
          "    def _rx_read(self, target) -> int:")
    assert _findings(root, "taint-integrity") == []
    assert _findings(root, "bad-waiver") == []


# --------------------------------------------- swrefine (DESIGN.md §22)
#
# Model<->code conformance: the canonical protocol-event vocabulary, the
# monitor automaton compiled from both engines' extracted machines, the
# checked-in event corpus, and transition coverage.  The runtime half
# (real rings, divergence classes, STARWAY_MONITOR) lives in
# tests/test_refine.py.


def test_refine_rules_registered():
    assert "refine" in analysis.RULES
    assert "monitor-coverage" in analysis.RULES
    from starway_tpu.analysis import PASSES

    assert "refine" in PASSES


def test_refine_head_clean_with_real_corpus():
    # The acceptance bar: monitor compiles from HEAD's machines, the
    # checked-in corpus (>= the floor) replays clean, every model
    # transition is witnessed or waived, and every divergence class is
    # pinned.
    from starway_tpu.analysis import refine

    assert analysis.run_all(REPO, ["refine"]) == []
    mon, problems = refine.compile_monitor(REPO)
    assert mon is not None and not problems
    assert len(mon.transitions) >= 20, sorted(mon.transitions)
    sink: list = []
    cases = refine.load_corpus(sink, REPO)
    assert sink == [] and len(cases) >= refine.CORPUS_FLOOR


async def _refine_floor_scenario(port):
    """Quick live scenario whose rings must witness COVERAGE_FLOOR: a
    session pair exchanging bursts through a FaultProxy with one
    mid-burst kill (suspend -> resume) -- the same shape as the chaos
    soaks, bounded for the gate."""
    import asyncio

    import numpy as np

    from starway_tpu import Client, Server
    from starway_tpu.testing.faults import FaultProxy

    server = Server()
    server.listen("127.0.0.1", port)
    proxy = FaultProxy("127.0.0.1", port).start()
    client = Client()
    await client.aconnect("127.0.0.1", proxy.port)
    try:
        for cycle in range(2):
            tag0 = cycle * 100
            bufs = [np.zeros(256, dtype=np.uint8) for _ in range(5)]
            recvs = [server.arecv(bufs[i], tag0 + i, (1 << 64) - 1)
                     for i in range(5)]
            sends = [client.asend(
                np.full(256, (tag0 + i) % 251, dtype=np.uint8), tag0 + i)
                for i in range(5)]
            if cycle == 1:
                await asyncio.sleep(0.2)
                proxy.kill_all(rst=True)
            await asyncio.wait_for(asyncio.gather(*sends), 30)
            await asyncio.wait_for(client.aflush(), 30)
            await asyncio.wait_for(asyncio.gather(*recvs), 30)
    finally:
        await client.aclose()
        await server.aclose()
        proxy.stop()


@pytest.mark.parametrize("engine", ["python", "native"])
async def test_refine_live_transition_coverage_floor(port, monkeypatch,
                                                     engine):
    """The LIVE transition-coverage floor (ISSUE 15): quick scenarios on
    EACH engine must witness refine.COVERAGE_FLOOR through real rings --
    the corpus proves the monitor can see every arm, this proves the
    engine taps actually fire.  Failures name the unwitnessed
    transitions."""
    from starway_tpu.analysis import refine
    from starway_tpu.core import native, swtrace

    if engine == "native" and not native.available():
        pytest.skip("native engine not built")
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "1" if engine == "native" else "0")
    monkeypatch.setenv("STARWAY_PROTO_TRACE", "1")
    monkeypatch.setenv("STARWAY_SESSION", "1")
    monkeypatch.delenv("STARWAY_TRACE", raising=False)
    swtrace.reset()
    await _refine_floor_scenario(port)
    mon, problems = refine.compile_monitor(REPO)
    assert mon is not None, problems
    witnessed: set = set()
    for dump in swtrace.dump_all():
        viols, seen = mon.replay(dump["events"], label=dump["worker"])
        assert viols == [], [v.render() for v in viols]
        witnessed |= seen
    missing = [t for t in refine.COVERAGE_FLOOR if t not in witnessed]
    assert not missing, (
        f"{engine} engine never witnessed model transition(s) {missing} "
        f"(witnessed: {sorted(witnessed)})")


def test_refine_frame_name_drift_python_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/frames.py",
          '    T_SACK: "SACK",', '    T_SACK: "SACKZ",')
    _assert_caught(root, "refine", "canonical event name", "frames.py")


def test_refine_frame_name_drift_cpp_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          'case T_SACK: return "SACK";', 'case T_SACK: return "WRONG";')
    _assert_caught(root, "refine", "disagree on T_SACK", "frames.py")


def test_refine_native_table_gone_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "const char* proto_frame_name(uint8_t t) {",
          "const char* frame_name_x(uint8_t t) {")
    _assert_caught(root, "refine", "proto_frame_name() not found",
                   "sw_engine.cpp")


def test_refine_python_taps_gone_seeded(tmp_path):
    # An engine that loses its EV_PROTO taps makes every replay
    # vacuously green -- that is a finding, not a pass.
    root = _seed(tmp_path)
    p = root / "starway_tpu" / "core" / "conn.py"
    text = p.read_text()
    assert "EV_PROTO" in text
    p.write_text(text.replace("swtrace.EV_PROTO", "swtrace.EV_CONN_UP"))
    _assert_caught(root, "refine", "taps are gone", "conn.py")


def test_refine_engine_transition_mutation_turns_gate_red(tmp_path):
    """The refinement gap itself (ISSUE 15): remove one dispatch arm from
    BOTH engines consistently -- protomodel stays green (the machines
    still agree), but the pinned event history replays red: the model no
    longer matches the histories real engines produced."""
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "elif ftype == frames.T_BYE:", "elif ftype == 0xEE:")
    _edit(root, "native/sw_engine.cpp",
          "        // swcheck: state(estab, BYE, estab|expired)\n", "")
    assert _findings(root, "proto-state") == []  # still two equal machines
    _assert_caught(root, "refine", "session-bye-then-eof", "refine_corpus.txt")


def test_refine_corpus_floor_and_malformed_lines(tmp_path):
    # A truncated or garbled corpus is itself a finding, never a silent
    # skip (the seeded tree's own corpus shadows the checked-in one).
    root = _seed(tmp_path)
    adir = root / "starway_tpu" / "analysis"
    adir.mkdir(parents=True, exist_ok=True)
    (adir / "refine_corpus.txt").write_text(
        "# truncated corpus\n"
        "only-case | ok | st:estab rx:HELLO\n"
        "garbled line without pipes\n"
        "bad-expect | violation:made-up | st:estab\n")
    _assert_caught(root, "refine", "below the", "refine_corpus.txt")
    _assert_caught(root, "refine", "malformed corpus", "refine_corpus.txt")
    _assert_caught(root, "refine", "not `ok` or a known violation class",
                   "refine_corpus.txt")


def test_refine_expectation_flip_seeded(tmp_path):
    # A pinned-ok history that starts violating (or vice versa) is the
    # core regression signal: model and history must move together.
    root = _seed(tmp_path)
    adir = root / "starway_tpu" / "analysis"
    adir.mkdir(parents=True, exist_ok=True)
    real = (REPO / "starway_tpu" / "analysis" / "refine_corpus.txt").read_text()
    (adir / "refine_corpus.txt").write_text(real.replace(
        "viol-resume-from-estab | violation:no-transition |",
        "viol-resume-from-estab | ok |", 1))
    _assert_caught(root, "refine", "viol-resume-from-estab",
                   "refine_corpus.txt")


def test_refine_unwitnessed_transition_seeded(tmp_path):
    # monitor-coverage: drop the corpus cases that witness (estab, SNACK)
    # (padding to stay above the floor) -- the unwitnessed transition
    # must be named.
    root = _seed(tmp_path)
    adir = root / "starway_tpu" / "analysis"
    adir.mkdir(parents=True, exist_ok=True)
    real = (REPO / "starway_tpu" / "analysis" / "refine_corpus.txt").read_text()
    kept = [ln for ln in real.splitlines()
            if "rx:SNACK" not in ln]
    kept += [f"pad-{i} | ok | st:estab rx:HELLO rx:DATA down"
             for i in range(4)]
    (adir / "refine_corpus.txt").write_text("\n".join(kept) + "\n")
    _assert_caught(root, "monitor-coverage", "(estab, SNACK)",
                   "refine_corpus.txt")


def test_refine_coverage_waiver(tmp_path):
    # The shadow corpus's own line-1 waiver suppresses the coverage
    # finding -- the new rules are ordinary --rules waiver targets.
    root = _seed(tmp_path)
    adir = root / "starway_tpu" / "analysis"
    adir.mkdir(parents=True, exist_ok=True)
    real = (REPO / "starway_tpu" / "analysis" / "refine_corpus.txt").read_text()
    kept = [ln for ln in real.splitlines() if "rx:SNACK" not in ln]
    kept += [f"pad-{i} | ok | st:estab rx:HELLO rx:DATA down"
             for i in range(4)]
    (adir / "refine_corpus.txt").write_text(
        f"{_SWA}(monitor-coverage): exercising the waiver path\n"
        + "\n".join(kept) + "\n")
    assert _findings(root, "monitor-coverage") == []
    assert _findings(root, "bad-waiver") == []


# --------------------------------------------- swcost (DESIGN.md §23)

_GATHER_ANCHOR = "views, spans = self._gather_tx()"
_SENDMSG_ANCHOR = "ssize_t w = ::sendmsg(c->fd, &msg, MSG_NOSIGNAL);"


def _shadow_ledger(root: Path) -> Path:
    """Give the seeded tree its own cost_budgets.txt (ledger_path prefers
    the tree copy over the package fallback, wirefuzz-corpus style)."""
    adir = root / "starway_tpu" / "analysis"
    adir.mkdir(parents=True, exist_ok=True)
    dst = adir / "cost_budgets.txt"
    dst.write_text(
        (REPO / "starway_tpu" / "analysis" / "cost_budgets.txt").read_text())
    return dst


def _ledger_row(led: Path, engine: str, path: str, metric: str):
    """``(line with its newline, pinned value)`` of one ledger row, found
    by its fields: the file's column widths are the writer's business."""
    m = re.search(rf"^{engine}[ \t]+{path}[ \t]+{metric}[ \t]+(\d+)[ \t]*\n",
                  led.read_text(), re.M)
    assert m, f"fixture drift: no row {engine} {path} {metric} in {led.name}"
    return m.group(0), int(m.group(1))


def test_swcost_rules_registered():
    # The three new finding codes are waiver targets (--rules) and
    # render as problem-matcher rows like every pass.
    for rule in ("cost-budget", "cost-model", "cost-site"):
        assert rule in analysis.RULES, rule


def test_cost_py_syscall_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "n = self.sock.sendmsg(views)",
          "n = self.sock.sendmsg(views) + self.sock.send(b\"\")")
    _assert_caught(root, "cost-budget", "py eager_tx syscalls",
                   "cost_budgets.txt")


def test_cost_py_copy_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py", _GATHER_ANCHOR,
          _GATHER_ANCHOR + "\n                junk = b\"\".join(views)")
    _assert_caught(root, "cost-budget", "py eager_tx copies",
                   "cost_budgets.txt")


def test_cost_py_alloc_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py", _GATHER_ANCHOR,
          _GATHER_ANCHOR + "\n                junk = bytearray(4096)")
    _assert_caught(root, "cost-budget", "py eager_tx allocs",
                   "cost_budgets.txt")


def test_cost_py_lock_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py", _GATHER_ANCHOR,
          _GATHER_ANCHOR + "\n                self.worker._lock.acquire()")
    _assert_caught(root, "cost-budget", "py eager_tx locks",
                   "cost_budgets.txt")


def test_cost_cpp_syscall_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", _SENDMSG_ANCHOR,
          "::send(c->fd, \"\", 0, 0);\n    " + _SENDMSG_ANCHOR)
    _assert_caught(root, "cost-budget", "cpp eager_tx syscalls",
                   "cost_budgets.txt")


def test_cost_cpp_copy_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", _SENDMSG_ANCHOR,
          "memcpy(iov, iov, 0);\n    " + _SENDMSG_ANCHOR)
    _assert_caught(root, "cost-budget", "cpp eager_tx copies",
                   "cost_budgets.txt")


def test_cost_cpp_alloc_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", _SENDMSG_ANCHOR,
          "void* zz = malloc(1);\n    " + _SENDMSG_ANCHOR)
    _assert_caught(root, "cost-budget", "cpp eager_tx allocs",
                   "cost_budgets.txt")


def test_cost_cpp_lock_regression_seeded(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", _SENDMSG_ANCHOR,
          "std::lock_guard<std::mutex> zz(gather_mu);\n    "
          + _SENDMSG_ANCHOR)
    _assert_caught(root, "cost-budget", "cpp eager_tx locks",
                   "cost_budgets.txt")


def test_cost_ratchet_fires_on_improvement(tmp_path):
    # BEATING a pin is also red until the ledger is lowered: raise the
    # py eager_tx syscalls pin above the measured value and the gate
    # must demand the ratchet, not silently accept the slack.
    root = _seed(tmp_path)
    led = _shadow_ledger(root)
    row, pinned = _ledger_row(led, "py", "eager_tx", "syscalls")
    led.write_text(led.read_text().replace(
        row, f"py eager_tx syscalls {pinned + 2}\n", 1))
    _assert_caught(root, "cost-budget", "beats the pinned budget",
                   "cost_budgets.txt")


def test_cost_ledger_malformed_and_unknown_rows(tmp_path):
    root = _seed(tmp_path)
    led = _shadow_ledger(root)
    led.write_text(led.read_text()
                   + "py eager_tx syscalls noninteger\n"
                   + "py warp_tx syscalls 1\n")
    _assert_caught(root, "cost-model", "malformed ledger row",
                   "cost_budgets.txt")
    _assert_caught(root, "cost-model", "unknown surface",
                   "cost_budgets.txt")


def test_cost_ledger_missing_row(tmp_path):
    root = _seed(tmp_path)
    led = _shadow_ledger(root)
    row, _ = _ledger_row(led, "py", "eager_tx", "syscalls")
    led.write_text(led.read_text().replace(row, "", 1))
    _assert_caught(root, "cost-model", "no ledger row for py eager_tx",
                   "cost_budgets.txt")


def test_cost_refuses_vacuity_when_anchor_renamed(tmp_path):
    # A hot-path anchor disappearing must be loud (cost-model), never a
    # silently-zero vector ratified by the ledger.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py",
          "def kick_tx(", "def kick_tx_v2(")
    _assert_caught(root, "cost-model", "kick_tx", "conn.py")


def test_cost_refuses_vacuity_when_cpp_pump_arm_gone(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "if (c->rx_skip)", "if (c->rx_skip2)")
    _assert_caught(root, "cost-model", "pump_frames rx arms",
                   "sw_engine.cpp")


def test_cost_instrumentation_removed_seeded(tmp_path):
    # Deleting the §23 runtime twin turns the gate red even though no
    # static site count moved: the dynamic conformance test would be
    # vacuous without the counters.
    root = _seed(tmp_path)
    p = root / "native" / "sw_engine.cpp"
    text = p.read_text()
    assert "bump(counters.io_syscalls" in text
    p.write_text(text.replace("bump(counters.io_syscalls",
                              "bump(counters.bytes_tx_shadow"))
    _assert_caught(root, "cost-model", "runtime cost twin dark",
                   "sw_engine.cpp")


def test_cost_site_waiver_excludes_site(tmp_path):
    # A justified cost-site waiver on the new site's own line excludes
    # it at extraction time: the ledger pin holds and the gate is green.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/conn.py", _GATHER_ANCHOR,
          _GATHER_ANCHOR + "\n                junk = b\"\".join(views)"
          f"  {_SWA}(cost-site): exercising the waiver path")
    assert _findings(root, "cost-budget") == []
    assert _findings(root, "bad-waiver") == []


def test_cost_budget_waiver_on_ledger_row(tmp_path):
    # cost-budget findings anchor to the ledger row, so the in-place
    # waiver discipline works there like any source line.
    root = _seed(tmp_path)
    led = _shadow_ledger(root)
    led.write_text(led.read_text().replace(
        "py  eager_tx    syscalls  1",
        "py  eager_tx    syscalls  3  "
        f"{_SWA}(cost-budget): exercising the waiver path", 1))
    assert _findings(root, "cost-budget") == []
    assert _findings(root, "bad-waiver") == []


# ------------------- ISSUE 19: the swpulse contract surface (DESIGN §25)
#
# swpulse grew a histogram vocabulary (HIST_NAMES <-> kHistNames[]), a
# bucket resolution (HIST_BUCKETS <-> kHistBuckets), and a stall-reason
# vocabulary (STALL_REASONS <-> kStallReasons[]) -- all cross-engine
# contract surface held by the contract-pulse pass.


def test_hist_dropped_from_cpp(tmp_path):
    # Renaming a histogram in the C++ array alone fires on BOTH sides of
    # the set diff (a histogram added to one engine only).
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", '"flush_us",       //',
          '"flush_us_v2",    //')
    _assert_caught(root, "contract-pulse", "flush_us_v2", "sw_engine.cpp")
    _assert_caught(root, "contract-pulse", "'flush_us'", "swtrace.py")


def test_hist_added_to_python_only(tmp_path):
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/swtrace.py",
          '"msg_bytes",', '"msg_bytes",\n    "rtt_us",')
    _assert_caught(root, "contract-pulse", "rtt_us", "swtrace.py")


def test_hist_bucket_resolution_drift(tmp_path):
    # The bucket count IS the bucket-boundary contract (base-2 buckets):
    # shrinking the native array alone must fire.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp",
          "constexpr int kHistBuckets = 64;",
          "constexpr int kHistBuckets = 32;")
    _assert_caught(root, "contract-pulse", "kHistBuckets = 32", "swtrace.py")


def test_stall_reason_reworded(tmp_path):
    # Stall reports carry the reason string verbatim from either engine:
    # rewording one side alone must fire.
    root = _seed(tmp_path)
    _edit(root, "native/sw_engine.cpp", '"stall-credit",', '"stall-credits",')
    _assert_caught(root, "contract-pulse", "stall-credits", "sw_engine.cpp")


def test_hist_vocabulary_vacuity_guard(tmp_path):
    # An extractor that silently loses the vocabulary must be a finding,
    # never a vacuous pass.
    root = _seed(tmp_path)
    _edit(root, "starway_tpu/core/swtrace.py",
          "HIST_NAMES = (", "HIST_LABELS = (")
    _assert_caught(root, "contract-pulse", "HIST_NAMES tuple not found",
                   "swtrace.py")
