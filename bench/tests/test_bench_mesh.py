"""A cell on four chips is data alone: the harness builds the client-
sharded executor on a mesh and the reference spreads its client axis
over the same mesh.  Rehearsed on four virtual CPU devices."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from bench_tinycell import REPO


def test_a_four_chip_cell_runs_correct_on_four_devices(tmp_path):
    script = textwrap.dedent(f"""
        import json, sys, time
        from pathlib import Path
        sys.path[:0] = [{str(REPO)!r}, {str(REPO / 'src')!r},
                        {str(REPO / 'bench' / 'tests')!r}]
        import bench_tinycell as bt
        from bench import harness
        from repro.launch.mesh import make_client_mesh
        bt.register_tiny()
        root = bt.make_tiny_root(Path({str(tmp_path)!r}))
        for kind, key, value in (("workloads", "chips", 4),
                                 ("traffic", "n_active", 4)):
            p = root / kind / "tiny.json"
            p.write_text(json.dumps({{**json.loads(p.read_text()),
                                      key: value}}))
        lines = []
        r = harness.run_cell("tiny", 2 ** 31 + 3, 1.0, False,
                             t_start=time.perf_counter(), root=root,
                             log=lines.append,
                             mesh_fn=lambda m: make_client_mesh(
                                 m["n_active"]))
        print(json.dumps({{"result": r, "info": json.loads(lines[0])}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["result"]["correct"] is True, out["result"]["checks"]
    assert out["result"]["device"]["count"] == 4
    assert out["info"]["compiles_in_window"] == 0
