"""Dry-run machinery integration: lower+compile on a small fake mesh.

Runs in a subprocess because the device-count override must precede JAX
init (the real dry-run uses 512 devices; 8 suffice to exercise the sharding
rules, the sharder, and the roofline extraction end to end).
"""
import json
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax
from repro.configs import get_smoke_config
from repro.launch import dryrun as dr
from repro.roofline import analysis as ra

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
out = {}
for arch, shape in (("olmo-1b", "train_4k"), ("olmo-1b", "decode_32k"),
                    ("mixtral-8x7b", "train_4k")):
    cfg = dataclasses.replace(get_smoke_config(arch), n_layers=2)
    compiled, kind, _ = dr.lower_cell(cfg, shape, mesh)
    hlo = compiled.as_text()
    coll = ra.collective_bytes_from_hlo(hlo)
    ca = ra.cost_terms(compiled)
    out[f"{arch}:{shape}"] = {
        "kind": kind,
        "flops": ca["flops"],
        "has_collectives": any(v > 0 for v in coll.values()),
        "mem_gib": compiled.memory_analysis().temp_size_in_bytes / 2**30,
    }
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_small_mesh_end_to_end():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    res = json.loads(line[len("RESULT "):])
    assert res["olmo-1b:train_4k"]["kind"] == "train"
    assert res["olmo-1b:decode_32k"]["kind"] == "decode"
    for k, v in res.items():
        assert v["flops"] > 0, k
        assert v["has_collectives"], k  # sharded programs must communicate
