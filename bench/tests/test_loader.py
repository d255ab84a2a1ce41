"""A cell, a configuration and a per-layer metric added as new files are
found by name, with no existing file edited."""
import filecmp
import json
import os
import shutil
import types

from lib import harness

from tiny import ROOT


def test_new_cell_config_and_metric_are_found(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: (root / p).read_bytes()
              for p in ["BENCHMARK.json"]}
    cfgs = root / "bench" / "configs"
    # a new configuration: its file of sizes, builder and reference
    cfg = json.loads((cfgs / "smollm-360m.json").read_text())
    cfg.update(name="tiny-lm", num_hidden_layers=2)
    (cfgs / "tiny-lm.json").write_text(json.dumps(cfg))
    shutil.copy(cfgs / "smollm-360m.py", cfgs / "tiny-lm.py")
    shutil.copy(cfgs / "smollm-360m_ref.py", cfgs / "tiny-lm_ref.py")
    # a new traffic mix and a new metric reader
    wl = root / "bench" / "workloads"
    shutil.copy(wl / "smollm360m.silo.json", wl / "tiny_lm.silo.json")
    (root / "bench" / "metrics" / "answer_ms.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    # entries appended to BENCHMARK.json's lists
    bm = json.loads(before["BENCHMARK.json"])
    bm["configs"].append({"name": "tiny-lm", "source": "x",
                          "file": "bench/configs/tiny-lm.json",
                          "reduced": ["num_hidden_layers"], "why": "x"})
    bm["workloads"].append({"name": "tiny_lm.silo", "config": "tiny-lm",
                            "traffic": "tiny_lm.silo", "chips": 1,
                            "why": "x"})
    bm["per_layer"].append({"name": "answer_ms", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "x", "moves": "round_s",
                            "workloads": ["tiny_lm.silo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = harness.find_cell("tiny_lm.silo", str(root))
    assert cell.cfg["num_hidden_layers"] == 2
    assert cell.builder.__name__.endswith("tiny-lm")
    assert hasattr(cell.ref, "loss")
    assert "answer_ms" in [m["name"] for m in cell.per_layer]
    assert harness.metric_reader("answer_ms", str(root))(
        types.SimpleNamespace()) == 42.0
    # an old cell does not see the new cell's metric
    old = harness.find_cell("smollm360m.silo", str(root))
    assert "answer_ms" not in [m["name"] for m in old.per_layer]
    # no file of the original benchmark changed
    cmp = filecmp.dircmp(os.path.join(ROOT, "bench"), root / "bench",
                         ignore=["__pycache__"])
    assert not cmp.diff_files
    for sub in cmp.subdirs.values():
        assert not sub.diff_files
