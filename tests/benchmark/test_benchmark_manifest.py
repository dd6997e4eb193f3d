"""The manifest loader: the committed BENCHMARK.json passes, and a name, unit
or cell that breaks the contract's rules is refused before any run."""

import copy
import os

import pytest

from benchmark.harness import layers, manifest


@pytest.fixture()
def doc():
    return copy.deepcopy(manifest.load())


def test_committed_manifest_and_every_file_it_names(doc):
    assert doc["command"] == ["python3", "benchmark/run.py"]
    for entry in doc["workloads"]:
        cell = manifest.cell(doc, entry["name"])
        for path in (cell.config_file, cell.traffic_file, cell.cell_file):
            assert os.path.isfile(path), path
        config = manifest.read_json(cell.config_file)
        declared = next(c for c in doc["configs"] if c["name"] == cell.config)
        assert config["reduced"] == declared["reduced"]
        assert config["source"] == declared["source"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for metric in cell.per_layer:
            assert callable(layers.load_reader(metric["name"]))
            assert metric["moves"] in names
    size = os.path.getsize(manifest.MANIFEST)
    assert size < 64 * 1024


def change(doc, where, index, **values):
    doc[where][index].update(values)
    return doc


def quota(doc):
    """Four-chip cells the contract allows: a quarter of the cells, rounded
    down, and one always."""
    return max(1, len(doc["workloads"]) // 4)


def four_chip_cells(doc, count):
    for index in range(count):
        change(doc, "workloads", index, chips=4)
    return doc


@pytest.mark.parametrize("mutate,why", [
    (lambda d: change(d, "workloads", 0, name="mistral 7b/chat"), "not a name"),
    (lambda d: change(d, "workloads", 0, name="x" * 65), "not a name"),
    (lambda d: change(d, "end_to_end", 0, unit="tokens per second"), "unit"),
    (lambda d: change(d, "end_to_end", 0, unit="µs"), "unit"),
    (lambda d: change(d, "end_to_end", 0, bound=0.2), "bound"),
    (lambda d: change(d, "end_to_end", 0, source="program_counter"), "host_clock"),
    (lambda d: change(d, "end_to_end", 0, why="because"), "unknown keys"),
    (lambda d: change(d, "workloads", 1, chips=2), "chips"),
    (lambda d: four_chip_cells(d, quota(d) + 1), "quarter"),
    (lambda d: change(d, "workloads", 1, config="mistral-7b"), "twice"),
    (lambda d: change(d, "workloads", 0, why="two\nlines"), "one line"),
    (lambda d: change(d, "workloads", 0, config="nope"), "names no config"),
    (lambda d: change(d, "per_layer", 0, moves="tokens_per_s"), "do not report"),
    (lambda d: change(d, "per_layer", 0, workloads=["nope"]), "unknown cell"),
    (lambda d: change(d, "configs", 0, file="configs/outside.json"), "outside paths"),
    (lambda d: d.update(run_seconds=52) or d, "run_seconds"),
    (lambda d: d.update(extra=1) or d, "top-level"),
    (lambda d: d["end_to_end"].pop() and d, "setup_s"),
])
def test_loader_refuses(doc, mutate, why):
    with pytest.raises(manifest.ManifestError, match=why):
        manifest.validate(mutate(doc))


def test_the_quota_itself_is_allowed(doc):
    manifest.validate(four_chip_cells(doc, quota(doc)))


def test_unknown_cell_and_reader(doc):
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.cell(doc, "mistral-7b.nope")
    with pytest.raises(FileNotFoundError):
        layers.load_reader("no.such.metric")
