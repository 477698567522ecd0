"""On four (virtual CPU) devices: the expert-parallel prefill with the
daemon's plan exchange agrees with the reference, and the same run with
the exchange between chips left out is not correct."""

import json
import os
import subprocess
import sys

from bench import cells

SCRIPT = r"""
import json, sys
import jax
from bench import harness
from bench.tests import smoke
from repro.comm import all_to_all

seed = int(sys.argv[1])
cell = smoke.cell("prefill", mesh=True)
sound = harness.run(cell, seed, 1.5, False, jax.devices())
all_to_all.ALL_TO_ALL_IMPLS["plan"] = lambda x, slow_axis, fast_axes, **k: x
broken = harness.run(smoke.cell("prefill", mesh=True), seed, 1.5, False,
                     jax.devices())
print(json.dumps({"sound": sound["line"], "broken": broken["line"],
                  "info": sound["info"]}))
"""


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [cells.ROOT, os.path.join(cells.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, "41"],
                          cwd=cells.ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["info"]["ref_dropped_assignments"] > 0
    assert not out["broken"]["correct"], out["broken"]["checks"]
