"""The harness is driven by data: every cell builds from its files by name,
an added file is found with no code edit, and a run refuses the CPU."""
import json
import shutil

import pytest

from chipbench import check, model, run

DATA_DIRS = ("configs", "traffic", "placements", "metrics", "limits")


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


def test_every_cell_builds_from_its_files(bench):
    for wl in bench["workloads"]:
        cell = run.build_cell(bench, wl["name"], 2**31 + 7, smoke=True)
        assert cell.kind in ("train", "serve")
        assert set(check.limits(wl["name"]))  # a limit file with limits
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))
    for c in bench["configs"]:
        dims, raw = model.load_config(c["name"])
        assert raw["source"] == c["source"]
        assert f"chipbench/configs/{c['name']}.json" == c["file"]


def test_program_configs_agree_with_the_files(bench):
    from repro.configs import get_config

    for c in bench["configs"]:
        dims, _ = model.load_config(c["name"])
        model.check_program_config(dims, get_config(dims.program_arch))


def test_metrics_follow_the_workloads_keys(bench):
    e2e = {m["name"] for m in run.metrics_for(bench, "olmo-1b.decode.host-kv", False)}
    assert e2e == {"decode_tokens_per_s", "tpot_p95_ms", "peak_hbm_gb", "setup_s"}
    per_layer = {m["name"] for m in run.metrics_for(bench, "smollm-360m.decode.resident", True)}
    assert per_layer == {"mfu.decode", "device_idle_share.decode"}


def test_added_files_are_found_without_a_code_edit(bench, tmp_path):
    here = run.HERE
    for d in DATA_DIRS:
        shutil.copytree(here / d, tmp_path / d)
    raw = json.loads((here / "configs" / "smollm-360m.json").read_text())
    (tmp_path / "configs" / "smollm-copy.json").write_text(json.dumps(raw))
    mix = json.loads((here / "traffic" / "decode.resident.json").read_text())
    mix["gen_len"] = [8, 8]
    (tmp_path / "traffic" / "decode.short.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "emitted_tokens.py").write_text(
        "def read(run):\n    return run['tokens']\n"
    )
    name = "smollm-copy.decode.short"
    added = dict(bench)
    added["workloads"] = bench["workloads"] + [
        {"name": name, "config": "smollm-copy", "traffic": "decode.short", "chips": 1,
         "why": "added by a test"}
    ]
    added["per_layer"] = bench["per_layer"] + [
        {"name": "emitted_tokens", "unit": "tokens", "better": "higher",
         "source": "host_clock", "layer": "device", "moves": "decode_tokens_per_s",
         "workloads": [name]}
    ]
    cell = run.build_cell(added, name, 1, smoke=True, root=tmp_path)
    assert cell.kind == "serve" and cell.mix["gen_len"] == [8, 8]
    assert "emitted_tokens" in {m["name"] for m in run.metrics_for(added, name, True)}
    assert run.reader("emitted_tokens", root=tmp_path)({"tokens": 3}) == 3


def test_run_refuses_a_cpu(bench, capsys):
    rc = run.main(["--workload", bench["workloads"][0]["name"], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
