"""The control: the plain reference put in the program's place, computed
one step below the precision the configuration states, and judged by
the same comparison as the program. It has to come out not correct.
Each kind module (``kinds/<kind>.py``) says how its control is made.

    python3 chipbench/control.py --workload <cell> --seeds <n> <n> <n>

prints, for each seed, the numbers the comparison reads and whether each
passes its limit; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import generator, kinds  # noqa: E402


def readings(config: dict, mix: dict, seed: int) -> dict:
    """The control's numbers for the first call the traffic would make
    in a run with this seed."""
    call = generator.Traffic(config, mix, seed).next_call()
    return kinds.load(mix["kind"]).control(config, call)


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config = generator.load("configs", cell["config"])
    mix = generator.load("traffic", cell["traffic"])
    for seed in args.seeds:
        got = readings(config, mix, seed)
        fails = sorted(k for k, v in got.items() if v > config["limits"][k])
        print(json.dumps({"workload": args.workload, "seed": seed, "readings": got,
                          "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
