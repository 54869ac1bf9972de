"""A configuration, a traffic mix and a metric added as new files are found
by name from a benchmark description, with no edit to any existing file."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from traffic.generator import generate  # noqa: E402


def test_new_files_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    conf = json.loads((BENCH / "testdata" / "tiny.json").read_text())
    conf["name"] = "newmodel"
    (tmp_path / "configs" / "newmodel.json").write_text(json.dumps(conf))
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 2.0, "of": "requests"},
           "message_tokens": {"median": 10, "sigma": 0.3, "lo": 4, "hi": 20},
           "new_tokens": {"median": 5, "sigma": 0.3, "lo": 2, "hi": 8}, "max_context": 64}
    (tmp_path / "traffic" / "newmix.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.seconds\n")
    bench = {
        "workloads": [{"name": "newmodel.newmix", "config": "newmodel", "traffic": "newmix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "new_metric", "unit": "s", "better": "lower", "bound": 0.1,
                        "source": "host_clock"}],
        "per_layer": [{"name": "other", "unit": "%", "better": "higher", "source": "device_trace",
                       "layer": "x", "moves": "new_metric", "workloads": ["elsewhere"]}],
    }
    cell = run.find_cell(bench, "newmodel.newmix", bench_dir=tmp_path)
    assert cell.conf["name"] == "newmodel" and cell.mix == mix and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["new_metric"]
    assert cell.per_layer == []            # listed for another cell only
    reader = run.metric_module("new_metric", bench_dir=tmp_path).read

    class Fake:
        seconds = 7.0

    assert reader(Fake()) == 14.0
    assert len(generate(cell.mix, seed=1, seconds=5, vocab=cell.conf["vocab_size"]).requests) == 10


def test_every_entry_of_the_benchmark_has_its_files():
    bench = run.load_benchmark()
    for w in bench["workloads"]:
        cell = run.find_cell(bench, w["name"])
        assert (BENCH / "reference" / f"{cell.conf['family']}.py").exists()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.metric_module(m["name"]).read)
