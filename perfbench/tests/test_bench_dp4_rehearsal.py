"""The four-chip training cell needs data files only: a configuration, a
traffic mix with a batch-axis data-parallel sharding, streamed staging and a
reader service, and a limits file, run by the existing train driver on four
virtual CPU devices."""
import json
import os
import subprocess
import sys
import textwrap

import smoke

SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = {paths!r}
    import smoke
    from harness.main import run_cell

    if __name__ == "__main__":
        bench = smoke.make_root({root!r}, [("smoke-dp4", "phi4mini-train4k.steady", 4)],
                                traffic_overrides={overrides!r})
        r = run_cell("smoke-dp4", 2**31 + 21, 0.3, False,
                     started=time.perf_counter(), platform="cpu",
                     root={root!r}, bench_file=bench)
        print(json.dumps(r))
""")

DP4 = {"sharding": "data", "streaming": True, "backend": "process",
       "service": {"pool_workers": 2}, "max_workers": 2,
       "global_batch": 8, "microbatches": 2}


def test_four_chip_cell_from_files_alone(tmp_path):
    here = os.path.dirname(os.path.abspath(__file__))
    paths = [here, smoke.BENCH_DIR, os.path.join(smoke.CHECKOUT, "src")]
    script = tmp_path / "dp4.py"
    script.write_text(SCRIPT.format(paths=paths, root=str(tmp_path),
                                    overrides=DP4))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_ENABLE_COMPILATION_CACHE="false")
    p = subprocess.run([sys.executable, str(script)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
