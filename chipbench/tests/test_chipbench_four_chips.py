"""The four-chip configuration's harness path, rehearsed on four virtual
CPU devices at the smoke widths: weights sharded over its (1, 4)
("data", "model") mesh, served through the lease, compared with the
plain reference."""
import json
import os
import subprocess
import sys

from chipbench import HERE, ROOT

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from chipbench import harness
from chipbench.tests.smoke import smoke_cell
tp4 = harness.load_json(sys.argv[2])
cell = smoke_cell(arch=tp4["arch"], chips=tp4["chips"], mesh=tp4["mesh"],
                  batch=tp4["batch"], sliding_window=tp4["sliding_window"],
                  rope_theta=tp4["rope_theta"], num_attention_heads=8,
                  num_key_value_heads=4, head_dim=8)
out = harness.run_cell(cell, 2**31 + 51, 1.5, False, require_chip=False,
                       log=lambda s: None)
print(json.dumps(out.result))
"""


def test_tp4_config_serves_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cfg = os.path.join(HERE, "configs", "mistral-nemo-12b-tp4.json")
    p = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, cfg], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] and r["failed"] == 0
