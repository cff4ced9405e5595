"""The per-layer readers of the message stages (DESIGN.md §12;
``benchmark/layer_metrics/``), loaded through ``benchmark.harness.spec`` as
``benchmark/run.py`` loads them: a made-up ``obs`` in the shape the
transport runner builds (every key of ``perf.stage_snapshot()``'s delta
under ``obs["stages"]``), the value each must read, ``None`` on a tree that
records no such stage -- and that ``BENCHMARK.json`` names a reader file
and cells that exist."""

import pytest

from benchmark.harness import spec as S

STREAM, A2A = "hbm_duplex.stream_4m", "hbm_duplex.a2a_16m_x4"


def _obs(**stages) -> dict:
    """A window of 2 s of transport in which each given stage took
    ``(count, seconds)``; ``tx`` stands for what an older tree records."""
    stages["tx"] = (100, 0.5)
    return {"fw_seconds": 2.0, "bytes": 1 << 30, "rounds": 10,
            "stages": {k: {"count": c, "seconds": s, "bytes": 0, "gbps": 0.0}
                       for k, (c, s) in stages.items()}}


@pytest.mark.parametrize("metric, cells, layer, obs, value", [
    ("post_us_per_op", [STREAM, A2A], "transport API",
     _obs(post=(360, 0.0576)), 160.0),
    ("loop_hop_us_per_op", [STREAM, A2A], "transport API",
     _obs(post=(360, 0.05), loop_hop=(240, 0.036)), 150.0),
    ("issue_us_per_msg.a2a", [A2A], "device plane",
     _obs(post=(360, 0.05), issue=(120, 0.0132)), 110.0),
    ("land_us_per_msg.a2a", [A2A], "device plane",
     _obs(post=(360, 0.05), land=(120, 0.24)), 2000.0),
    ("settle_us_per_msg.a2a", [A2A], "engines",
     _obs(post=(360, 0.05), settle=(120, 0.0096)), 80.0),
    ("ring_wait_share.stream", [STREAM], "engines",
     _obs(post=(1290, 0.05), ring_wait=(400, 0.5)), 25.0),
    ("place_queue_us_per_msg.stream", [STREAM], "device plane",
     _obs(post=(1290, 0.05), place_queue=(640, 1.28)), 2000.0),
    ("fetch_start_us_per_msg.stream", [STREAM], "device plane",
     _obs(post=(1290, 0.05), fetch_start=(640, 0.4288)), 670.0),
])
def test_stage_reader(metric, cells, layer, obs, value):
    spec = S.load_spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span" and entry["moves"] == "xfer_GBps"
    assert entry["layer"] == layer and entry["workloads"] == cells
    assert (S.BENCH / "layer_metrics" / f"{metric}.py").is_file()
    known = {c["name"] for c in spec["workloads"]}
    assert set(cells) <= known
    e2e = next(m for m in spec["end_to_end"] if m["name"] == entry["moves"])
    assert set(cells) <= set(e2e["workloads"])
    for cell in cells:  # as run.py reads a cell's line
        assert metric in {m["name"] for m in S.per_layer_for(spec, cell)}

    read = S.load_reader(metric).read
    assert read(obs) == pytest.approx(value)
    assert read(_obs()) is None                 # the parent: no such stage
    assert read({"stages": None}) is None and read({}) is None
    got = S.read_layer_metrics(spec, cells[0], obs)[metric]
    assert got == {"value": pytest.approx(value), "unit": entry["unit"]}
    if metric == "ring_wait_share.stream":
        # A tree with the stages whose producer never blocked reads 0.
        assert read(_obs(post=(10, 0.01))) == 0.0
