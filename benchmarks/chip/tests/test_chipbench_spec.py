"""BENCHMARK.json names only what the harness can find and read."""

import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import blocks, system, traffic  # noqa: E402
from chipbench.cell import HERE, ROOT, load, reader  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    names = [c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(conf["changed_from_source"])
        assert not set(conf["departures"]) & set(conf["changed_from_source"])
        block = blocks.of(conf)
        dims = block.Dims.from_config(conf)
        assert dims.vocab == conf["vocab_size"]
        assert block.dims_of(system.model_config(conf, "full")) == dims  # the program runs the file
    for w in SPEC["workloads"]:
        t = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
        assert len(traffic.schedule(t, 1, 5, 100)) == round(5 * t["rate_per_s"])
        assert t["prompt_tokens"] + t["output_tokens"]["max"] <= t["max_len"]


def test_every_cell_reports_what_it_must():
    for w in SPEC["workloads"]:
        cell = load(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert callable(reader(m["name"]))
