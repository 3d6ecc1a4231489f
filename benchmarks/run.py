"""The benchmark of pathway_tpu's device plane.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for. Prints one JSON object as the
last line of standard output; exits non-zero, with no result line,
without a TPU. ``--control fp8`` (or ``int8``) also puts the reference in
that precision in the program's place and reports whether the comparison
fails it (PERF.md, section 2); the driver's runs never pass it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: the program and `benchmarks`


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", choices=("fp8", "int8"), default=None)
    parser.add_argument("--dump-trace", default=None, help="write the traced run's events here (json.gz)")
    args = parser.parse_args(argv)

    from benchmarks.lib import runner, spec

    cell = spec.load_cell(args.workload)
    result = runner.run_cell(
        cell,
        args.seed,
        args.seconds,
        bool(args.trace),
        control=args.control,
        t_process=T_PROCESS,
        dump_trace=args.dump_trace,
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # threads of the program (metrics, rings) may outlive main(); leave
    # through os._exit once the result line is out
    code = 1
    try:
        code = main()
    except SystemExit as e:
        if e.code not in (None, 0):
            print(e, file=sys.stderr)
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
