"""Run one benchmark cell once; see bench/harness/cli.py and PERF.md.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
import pathlib
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(t_start=T_START))
