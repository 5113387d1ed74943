#!/usr/bin/env python3
"""How fast the resume cell's checkpoint reads from disk, cold.

    python3 perfbench/cold_probe.py [--seed N]

Writes the checkpoint of ``phi4mini-resume.warm`` (the cell's own writer and
state), evicts it from the page cache and checks with mincore that it is
gone, then times one plain sequential read of the file and one
``train/checkpoint.restore_arrays`` of it, each from a cold cache, and one
warm ``restore_arrays`` beside them. Prints one JSON line. This sizes a
cold-resume cell; the benchmark's runs do not run it."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

CELL = "phi4mini-resume.warm"
CHUNK = 64 << 20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness import device, pagecache
    from harness.cells import CHECKOUT, load_cell
    from harness.main import log, work_dir

    cell = load_cell(CELL)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        log(str(e))
        return 2
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from repro.train.checkpoint import restore_arrays

    workdir = work_dir()
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        res = cell.driver().Resumer(cell.config, cell.traffic, devices,
                                    workdir)
        res.write(args.seed)
        size = os.path.getsize(res.path)
        out = {"file_bytes": size, "device": device.describe(devices)}

        out["evicted_for_read"] = pagecache.cold(res.path)
        out["resident_before_read"] = pagecache.residency(res.path)
        t = time.perf_counter()
        with open(res.path, "rb", buffering=0) as f:
            while f.read(CHUNK):
                pass
        out["plain_read_s"] = time.perf_counter() - t
        out["plain_read_gb_per_s"] = size / out["plain_read_s"] / 1e9

        out["evicted_for_restore"] = pagecache.cold(res.path)
        out["resident_before_restore"] = pagecache.residency(res.path)
        for name in ("cold", "warm"):
            t = time.perf_counter()
            arrays, _ = restore_arrays(res.path, **res.kw)
            out[f"restore_arrays_{name}_s"] = time.perf_counter() - t
            del arrays
        out["restore_arrays_cold_gb_per_s"] = (
            size / out["restore_arrays_cold_s"] / 1e9)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
