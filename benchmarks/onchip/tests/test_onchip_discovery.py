"""The harness finds every configuration, mix and metric by name: a new
cell needs new files and one ``workloads`` entry, and no edit to a file
that is there."""

import json
import os
import shutil
from types import SimpleNamespace

import _path
import generator
import spec

BENCH_JSON = os.path.join(_path.ROOT, "BENCHMARK.json")


def test_every_named_file_exists():
    bench = json.load(open(BENCH_JSON))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.adapter() and cell.reference()
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for c in bench["configs"]:
        conf = json.load(open(os.path.join(_path.ROOT, c["file"])))
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert conf["published"][key] != conf[key]


def test_new_cell_is_found_by_name(tmp_path):
    base = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "adapters", "references"):
        shutil.copytree(os.path.join(_path.BENCH, d), base / d)
    bench = json.load(open(BENCH_JSON))
    for c in bench["configs"]:               # the copy's own files
        c["file"] = c["file"].replace("benchmarks/onchip/", "bench/")
    # the new files: a configuration, a mix and a metric
    conf = json.load(open(base / "configs" / "qwen3-1.7b.json"))
    conf["name"] = "qwen3-1.7b-copy"
    json.dump(conf, open(base / "configs" / "qwen3-1.7b-copy.json", "w"))
    mix = json.load(open(base / "traffic" / "chat-shared.json"))
    mix["rate_rps"] = 3.0
    json.dump(mix, open(base / "traffic" / "chat-faster.json", "w"))
    (base / "metrics" / "requests_seen.new.py").write_text(
        "def read(ctx):\n    return float(len(ctx.recs))\n")
    # ... and entries that name them
    bench["configs"].append({
        "name": "qwen3-1.7b-copy", "source": conf["source"],
        "file": "bench/configs/qwen3-1.7b-copy.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "qwen3-1.7b-copy.chat-faster", "config": "qwen3-1.7b-copy",
        "traffic": "chat-faster", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "requests_seen.new", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "front end",
        "moves": "output_tok_s", "workloads": ["qwen3-1.7b-copy.chat-faster"]})
    path = tmp_path / "BENCHMARK.json"
    json.dump(bench, open(path, "w"))

    cell = spec.load_cell("qwen3-1.7b-copy.chat-faster", str(path), str(base))
    assert cell.config["name"] == "qwen3-1.7b-copy"
    assert cell.traffic["rate_rps"] == 3.0
    assert [m["name"] for m in cell.per_layer if m["name"].endswith(".new")] \
        == ["requests_seen.new"]
    assert cell.reader("requests_seen.new")(SimpleNamespace(recs=[1, 2])) \
        == 2.0
    plan = generator.build(cell.traffic, 256, 1, 10)
    assert len(plan.arrivals) == 3 * (cell.traffic["lead_in_s"] + 10)
    # the existing cells still load, unchanged
    old = spec.load_cell("qwen3-8b-l12.halo-longctx", str(path), str(base))
    assert "requests_seen.new" not in [m["name"] for m in old.per_layer]
