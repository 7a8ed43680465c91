"""Each cell of BENCHMARK.json, at a tiny width, through the harness's
whole run on the CPU (Pallas kernels in interpret mode); and a new
configuration, traffic mix and metric found by name alone."""

import json
import os

import pytest

import bench_cpu
from bench import peaks, run, spec

V5E = peaks.PEAKS["TPU v5 lite"]


def tiny_cells():
    """One tiny cell per real cell, with the real cell's offload choice."""
    real = json.load(open(os.path.join(bench_cpu.ROOT, "BENCHMARK.json")))
    out = {}
    for w in real["workloads"]:
        cell = json.load(open(os.path.join(bench_cpu.ROOT, "bench", "cells",
                                           f"{w['name']}.json")))
        out[w["name"]] = bool(cell["engine"]["offload"])
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_cpu.make_root(tmp_path_factory.mktemp("cells"),
                               cells=tiny_cells())


@pytest.mark.parametrize("workload", sorted(tiny_cells()))
def test_cell_runs_end_to_end(root, workload):
    cell = spec.resolve(workload, root)
    res = run.run(cell, 2 ** 31 + 11, 1.5, False, require_tpu=False,
                  peaks=V5E)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {x.name for x in cell.end_to_end}
    assert m["output_tok_s"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    assert list(res)[-2] == "check" and res["readings"]["tokens"] > 0
    assert res["readings"]["greedy_tokens"] > 0


def test_new_files_are_found_by_name(tmp_path):
    """A cell, a traffic mix and a per-layer metric added as files, with
    their entries, run without any edit to the harness."""
    root = bench_cpu.make_root(tmp_path, cells={"new.cell": False})
    conf = dict(bench_cpu.TINY, name="tiny-new", num_hidden_layers=1)
    json.dump(conf, open(tmp_path / "bench" / "configs" / "tiny-new.json",
                         "w"))
    mix = dict(bench_cpu.TRAFFIC, greedy_share=1.0)
    json.dump(mix, open(tmp_path / "bench" / "traffic" / "greedy.json", "w"))
    (tmp_path / "bench" / "metrics" / "extra.answer.py").write_text(
        "def read(rec):\n    return 42.0 + 0 * rec.window.steps\n")
    b = json.load(open(tmp_path / "BENCHMARK.json"))
    b["configs"].append({"name": "tiny-new", "source": "test",
                         "file": "bench/configs/tiny-new.json",
                         "reduced": [], "why": "test"})
    b["workloads"] = [{"name": "new.cell", "config": "tiny-new",
                       "traffic": "greedy", "chips": 1, "why": "test"}]
    b["per_layer"].append({"name": "extra.answer", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "output_tok_s",
                           "workloads": ["new.cell"]})
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))
    cell = spec.resolve("new.cell", root)
    assert cell.config["num_hidden_layers"] == 1
    assert cell.traffic["greedy_share"] == 1.0
    assert "extra.answer" in [m.name for m in cell.per_layer]
    res = run.run(cell, 7, 1.0, True, require_tpu=False, peaks=V5E)
    m = res["metrics"]
    assert m["extra.answer"] == {"value": 42.0, "unit": "1"}
    assert m["sched.decode_rows_per_tick"]["value"] > 0
    assert 0 < m["kv.pages_used_frac"]["value"] <= 100
    assert 0 < m["step.mfu"]["value"] < 100
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"], res["check"]


def test_unknown_names_are_refused(tmp_path):
    root = bench_cpu.make_root(tmp_path)
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.resolve("no.such.cell", root)
    os.remove(tmp_path / "bench" / "metrics" / "step.mfu.py")
    with pytest.raises(spec.SpecError, match="step.mfu"):
        spec.resolve("tiny.prefill", root)
