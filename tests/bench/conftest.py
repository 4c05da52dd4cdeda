"""Shared set-up of the benchmark's tests: the repository root on the
path (for `bench`), and a smoke-size copy of an MLP cell that the CPU can
run."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SMOKE_FLAGS = ["--smoke", "--examples", "1024", "--batch", "16",
               "--score-batch", "32"]


def smoke_cell(workload: str = "mlp_svhn.score_heavy") -> dict:
    """The cell's files as the harness loads them, cut to the trainer's
    `--smoke` MLP (64 -> 128 -> 128 -> 10) and 1,024 rows."""
    from bench import run
    c = copy.deepcopy(run.load_cell(workload))
    flags = c["cell"]["flags"]
    for name, value in zip(SMOKE_FLAGS[1::2], SMOKE_FLAGS[2::2]):
        flags[flags.index(name) + 1] = value
    flags.insert(0, "--smoke")
    c["cell"]["variance_steps"] = [32, 40]
    c["config"].update(input_dim=64, hidden=[128, 128])
    return c
