"""Entry point: ``python3 perfbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the checkout's root (see
`perfbench/harness.py`)."""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from perfbench import harness
    sys.exit(harness.main(T_START))
