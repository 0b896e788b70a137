"""``tools/readings_dp.py`` with the faults of ``tests/faults_val.py``:

    python3 benchmarks/tools/readings_val.py --val-fault ties_by_position \\
        --workload criteo255q.trainval --seeds 1,2 [--rows N] [--out f.jsonl]

plants the named fault, then hands the remaining arguments to
``readings_dp.py`` (whose own ``--fault`` knows ``faults.py`` and
``faults_dp.py``; without ``--val-fault`` this is ``readings_dp.py``). Not
part of a benchmark run.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "tests"),
             HERE):
    sys.path.insert(0, path)


def main() -> int:
    argv = sys.argv[1:]
    if "--val-fault" in argv:
        at = argv.index("--val-fault")
        name = argv[at + 1]
        del argv[at:at + 2]
        import faults_val
        faults_val.plant(name)
        print(f"planted {name}", file=sys.stderr, flush=True)
    sys.argv[1:] = argv
    import readings_dp
    return readings_dp.main()


if __name__ == "__main__":
    sys.exit(main())
