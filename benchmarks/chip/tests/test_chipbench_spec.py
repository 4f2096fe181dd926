"""Finding a cell's parts by name, the peaks table, and BENCHMARK.json."""
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
from chipbench import control, drive, reference, spec, streams  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark's files, to add new ones to."""
    dst = tmp_path / "chip"
    shutil.copytree(CHIP, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    return str(dst)


def test_new_config_mix_and_metric_are_found_by_name(root):
    """A later cell adds files only: a configuration, a mix and a metric
    reader, named in BENCHMARK.json; the harness finds each by name."""
    cfg = spec.load_config("synth_seq5", root)
    cfg["name"] = "synth_seq3"
    cfg["query"] = "SELECT * FROM S WHERE A1 ; A2 ; A3 WITHIN 100 events"
    with open(os.path.join(root, "configs", "synth_seq3.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "traffic", "burst.json"), "w") as f:
        json.dump({"name": "burst", "loop": "open", "rate": 123.0}, f)
    with open(os.path.join(root, "metrics", "chunks.completed.py"), "w") as f:
        f.write("def read(run):\n    return len(run.completed())\n")
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "synth_seq3.burst",
                               "config": "synth_seq3", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "chunks.completed", "unit": "chunks",
                               "better": "higher", "source": "host_clock",
                               "layer": "service", "moves": "events_per_s",
                               "workloads": ["synth_seq3.burst"]})
    cell = spec.find_cell(bench, "synth_seq3.burst")
    assert spec.load_config(cell["config"], root)["query"].startswith(
        "SELECT * FROM S WHERE A1 ; A2 ; A3")
    assert spec.load_mix(cell["traffic"], root)["rate"] == 123.0
    readers = spec.metric_readers(bench, cell["name"], root)
    assert list(readers) == ["chunks.completed"]
    run = drive.Run(seconds=1.0,
                    t_open=0.0, t_close=1.0,
                    chunks=[(0.1, 0.5, 8), (0.6, 1.5, 8)])
    assert readers["chunks.completed"](run) == 1
    # the cells already there keep their metrics
    assert "device.idle_share" in spec.metric_readers(
        bench, "stock_q3.replay", root)


FIG8 = {
    "name": "fig8_w3200",
    "source": "CORE arXiv:2111.04635 §6 Fig. 8: A1;A2;A3 WITHIN w events, "
              "A3 absent from the stream",
    "reduced": [],
    "query": "SELECT * FROM S WHERE A1 ; A2 ; A3 WITHIN 3200 events",
    "reference": {"atoms": [{"type": "A1"}, {"type": "A2"}, {"type": "A3"}],
                  "partition_by": None,
                  "window": {"kind": "events", "size": 3200},
                  "consume": False},
    "generator": {"kind": "cycle", "types": ["A1", "A2", "B1"]},
    "engine": {"kind": "single_small", "chunk_len": 128, "ring": None,
               "arena_capacity": None, "strict_overflow": True},
    "service": {"checkpoint_every": 8, "queue_chunks": 8},
    "sink": {"enumerate": False},
    "control": {"kind": "count_one_more"},
}

#: a generator kind, an engine kind and a control kind, as files
NEW_KINDS = {
    "generators/cycle.py": (
        "import numpy as np\n"
        "def types(gen):\n    return list(gen['types'])\n"
        "def draw(s, n):\n"
        "    return {'type': np.arange(s.n, s.n + n) % len(s.type_names)}\n"),
    "engines/single_small.py": (
        "def build(cfg):\n"
        "    from repro.core import compile_query\n"
        "    from repro.vector import StreamingVectorEngine, VectorEngine\n"
        "    return StreamingVectorEngine(\n"
        "        VectorEngine(compile_query(cfg['query'])),\n"
        "        chunk_len=cfg['engine']['chunk_len'], batch=1)\n"),
    "controls/count_one_more.py": (
        "from chipbench import reference\n"
        "def outputs(cfg, cols, type_names, n):\n"
        "    counts, ces = reference.evaluate(cfg['reference'], cols,\n"
        "                                     type_names, n)\n"
        "    return counts + 1, ces\n"),
}


def test_a_new_configuration_of_new_kinds_is_files_only(root):
    """Fig. 8's window sweep point, with a generator, an engine and a
    control of kinds the harness has never seen, each a new file: the
    stream, the reference, the engine and the control are all found."""
    with open(os.path.join(root, "configs", "fig8_w3200.json"), "w") as f:
        json.dump(FIG8, f)
    for rel, text in NEW_KINDS.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    cfg = spec.load_config("fig8_w3200", root)
    s = streams.Stream(cfg["generator"], 2 ** 40 + 1, root)
    s.grow(1000)
    assert s.type_names == ["A1", "A2", "B1"]
    assert s.raw(4) == {"type": "A2"}
    counts, _ = reference.evaluate(cfg["reference"], s.select(np.arange(999)),
                                   s.type_names, 999)
    assert not counts.any()                 # A3 never comes
    engine = drive.build_engine(cfg, root)
    assert engine.chunk_len == 128
    sound = control.readings(cfg, 5, 1024, control=False, root=root)
    broken = control.readings(cfg, 5, 1024, control=True, root=root)
    assert all(v == 0 for v, _ in sound.values())
    assert broken["count_diff"][0] == 1024


def test_a_mix_sizes_the_ingress_buffer_of_its_sources():
    """The mix's ``queue_chunks`` wins over the configuration's; a mix that
    gives none keeps the configuration's.  The open loop's buffer holds
    two seconds of its arrivals, so a host stall sheds nothing."""
    cfg = spec.load_config("synth_seq5")
    assert drive.ingress_chunks(cfg, spec.load_mix("replay")) == \
        cfg["service"]["queue_chunks"]
    steady = spec.load_mix("steady")
    chunks = drive.ingress_chunks(cfg, steady)
    assert chunks == steady["queue_chunks"]
    assert chunks * cfg["engine"]["chunk_len"] / steady["rate"] >= 2.0


def test_names_cannot_leave_their_directory(root):
    for bad in ("../run", "a/b", ".hidden", ""):
        for load in (spec.load_config, spec.load_mix, spec.load_generator,
                     spec.load_engine, spec.load_control, spec.load_reader):
            with pytest.raises(ValueError):
                load(bad, root)


def test_peaks_refuse_an_unknown_device_kind():
    v5e = spec.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in v5e["source"]
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(KeyError):
            spec.load_peaks(kind)


def test_benchmark_json_is_complete():
    """Every name is a name, every cell's files and every reader exist,
    and every cell reports set-up, another end-to-end metric and a
    per-layer metric."""
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    cfg_names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        full = spec.load_config(c["name"])
        assert full["source"] == c["source"] and full["reduced"] == \
            c["reduced"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["config"] in cfg_names
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
        spec.load_mix(cell["traffic"])
        reported = {m["name"] for m in spec.cell_metrics(bench, cell["name"],
                                                         trace=False)}
        assert "setup_s" in reported and len(reported) >= 2
        layer = spec.cell_metrics(bench, cell["name"], trace=True)
        assert layer
        for m in layer:
            assert m["moves"] in reported, (cell["name"], m["name"])
        spec.metric_readers(bench, cell["name"])
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
