r"""A traced run that also keeps what the trace holds, for reading it by
hand: chiprun_out/<out>/summary.txt and a small cut of the table.

    python3 benchmark/tools/dump_trace.py <out> --workload <cell> \
        --seed 1 --seconds 20
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run   # noqa: E402

if __name__ == "__main__":
    out = os.path.join(ROOT, "chiprun_out", sys.argv[1])
    sys.exit(run.main(sys.argv[2:] + ["--trace", "1"],
                      overrides={"keep_trace": out}))
