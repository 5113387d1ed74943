"""The controls at smoke size on the CPU: the reference put in the
program's place at the next precision down fails the cell's limits, where
the program passes them; likewise a step that leaves out half its batch."""
import json

import smoke


def readings(tmp_path, cell, **seeds):
    import control

    root = str(tmp_path)
    smoke.make_root(root, [(cell, cell, 1)])
    out = tmp_path / "readings.json"
    args = ["--workload", cell, "--platform", "cpu", "--root", root,
            "--out", str(out)]
    for k, v in seeds.items():
        args += [f"--{k.replace('_', '-')}", ",".join(map(str, v))]
    assert control.main(args) == 0
    with open(out) as f:
        return json.load(f)["rows"]


def limits(cell):
    if cell in smoke.SMOKE_LIMITS:
        return smoke.SMOKE_LIMITS[cell]
    with open(f"{smoke.BENCH_DIR}/cells/{cell}.json") as f:
        return json.load(f)["limits"]


def failed(values, lim):
    return [k for k, v in values.items() if v > lim[k]]


def test_train_control_and_half_batch_fail(tmp_path):
    cell = "phi4mini-train4k.steady"
    rows = readings(tmp_path, cell, seeds=[2**31 + 3], control_seeds=[2**31 + 3],
                    fault_seeds=[2**31 + 3])
    lim = limits(cell)
    (row,) = rows
    assert failed(row["program"], lim) == []
    assert failed(row["control"], lim), row["control"]
    assert failed(row["half_batch"], lim), row["half_batch"]


def test_resume_control_fails(tmp_path):
    cell = "phi4mini-resume.warm"
    (row,) = readings(tmp_path, cell, seeds=[7], control_seeds=[7])
    lim = limits(cell)
    assert failed(row["program"], lim) == []
    assert failed(row["control"], lim) == ["leaf_mismatch_words",
                                           "fingerprint_mismatches"]
