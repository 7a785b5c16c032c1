"""Every piece of a cell is found by name; a new cell, and a new
architecture family, is new files only."""
import json
import shutil

import numpy as np

from conftest import BENCH, REDUCED
from chipbench import discover, weights


def test_benchmark_cells_resolve():
    bench = discover.benchmark()
    names = discover.cells()
    for w in bench["workloads"]:
        assert w["name"] in names
        c = discover.cell(w["name"])
        assert c["cell"]["config"] == w["config"]
        assert c["cell"]["traffic"] == w["traffic"]
        assert c["cell"]["chips"] == w["chips"]
        assert callable(c["generator"].schedule)
        fam = discover.family(c["arch"])
        assert fam.name == c["arch"]["family"]
        assert callable(fam.reference.hidden)
        assert callable(fam.adapter.params)
        for group in ("end_to_end", "per_layer"):
            for m in discover.metric_names(bench, w["name"], group):
                assert callable(discover.metric(m).read)


def test_config_files_are_the_benchmark_files():
    bench = discover.benchmark()
    for c in bench["configs"]:
        arch = json.loads((BENCH.parent / c["file"]).read_text())
        assert arch["source"] == c["source"]
        assert arch["reduced"] == c["reduced"]


def test_a_new_cell_is_found_from_new_files_alone(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    mix = json.loads((base / "traffic" / "chat.json").read_text())
    mix["generator"] = "open_loop_copy"
    mix["arrivals"]["cv"] = 3.0
    (base / "traffic" / "burst.json").write_text(json.dumps(mix))
    shutil.copy(base / "generators" / "open_loop.py",
                base / "generators" / "open_loop_copy.py")
    arch = json.loads((base / "configs" / "qwen3-1.7b.json").read_text())
    (base / "configs" / "qwen3-1.7b-copy.json").write_text(json.dumps(arch))
    cell = json.loads((base / "cells" / "qwen3-1.7b.chat.json").read_text())
    cell.update(config="qwen3-1.7b-copy", traffic="burst")
    (base / "cells" / "qwen3-1.7b-copy.burst.json").write_text(
        json.dumps(cell))
    (base / "metrics" / "tokens_per_step.burst.py").write_text(
        "def read(ctx):\n    return 1.0\n")

    assert "qwen3-1.7b-copy.burst" in discover.cells(base)
    c = discover.cell("qwen3-1.7b-copy.burst", base)
    assert c["mix"]["arrivals"]["cv"] == 3.0
    assert c["arch"]["hidden_size"] == 2048
    sched = c["generator"].schedule(c["mix"], rate=1.0, seconds=5,
                                    seed=1, vocab=100, max_total=2047)
    assert sched
    assert discover.metric("tokens_per_step.burst", base).read(None) == 1.0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_new_family_is_found_from_new_files_alone(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    for part in ("reference", "adapters"):
        shutil.copy(base / part / "dense_gqa.py",
                    base / part / "dense_gqa_copy.py")
    arch = json.loads((base / "configs" / "qwen3-1.7b.json").read_text())
    arch["family"] = "dense_gqa_copy"
    (base / "configs" / "qwen3-1.7b-fam.json").write_text(json.dumps(arch))
    cell = json.loads((base / "cells" / "qwen3-1.7b.chat.json").read_text())
    cell["config"] = "qwen3-1.7b-fam"
    (base / "cells" / "qwen3-1.7b-fam.chat.json").write_text(
        json.dumps(cell))

    c = discover.cell("qwen3-1.7b-fam.chat", base)
    fam = discover.family(c["arch"], base)
    assert fam.name == "dense_gqa_copy"
    assert fam.reference.__file__ == str(
        base / "reference" / "dense_gqa_copy.py")
    assert fam.adapter.__file__ == str(
        base / "adapters" / "dense_gqa_copy.py")
    small = dict(c["arch"], **REDUCED)
    dense = dict(small, family="dense_gqa")
    cfg = fam.adapter.model_config("qwen3-1.7b", small)
    assert cfg == discover.family(dense).adapter.model_config(
        "qwen3-1.7b", dense)
    w = weights.make(small, 7, cfg.padded_vocab, base=base)
    w0 = weights.make(dense, 7, cfg.padded_vocab)
    assert sorted(w) == sorted(w0)
    for k in w:
        np.testing.assert_array_equal(np.asarray(w[k]), np.asarray(w0[k]))
    tokens = np.arange(24) % small["vocab_size"]
    np.testing.assert_array_equal(
        np.asarray(fam.reference.hidden(small, w, tokens)),
        np.asarray(discover.family(dense).reference.hidden(dense, w0,
                                                           tokens)))
    assert all(p.read_bytes() == b for p, b in before.items())
