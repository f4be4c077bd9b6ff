"""The precision control of a cell: the reference at 4-bit impacts in the program's place.

    python3 bench/control.py --workload passage-isn.safe --seeds 5 6 7

The configuration states 8-bit impacts; the control computes every answer
of a run's queries exhaustively at 4 bits (int4 for int8) and hands them
to the same check a run makes. It must come out not correct: the count of
wrong answers it prints is the upper reading the limit 0 sits below. It
needs no chip; it reads nothing of the program.
"""

import sys
import time
from pathlib import Path

sys.path[:1] = [str(Path(__file__).resolve().parent.parent)]

import argparse  # noqa: E402
import json  # noqa: E402

from bench import gen  # noqa: E402
from bench.reference import Answer  # noqa: E402
from bench.spec import load_cell  # noqa: E402

CONTROL_BITS = 4


def control_reading(cell, seed: int, seconds: float) -> dict:
    cfg, inputs = cell.config, cell.inputs
    corpus = inputs.make_corpus(cfg, seed)
    order, ends = inputs.arrange(corpus)
    due = gen.make_schedule(cell.traffic, seconds, seed)
    queries = inputs.make_queries(corpus, cell.traffic, len(due), seed)
    ref = cell.reference.make_reference(corpus, order, ends, cfg)
    low = cell.reference.make_reference(corpus, order, ends, dict(cfg, impact_bits=CONTROL_BITS))
    wrong = 0
    for q in queries:
        ids, sc = low.expected(q, None, "rank_safe")
        wrong += not ref.check(q, Answer(ids, sc, len(ends), "exhausted"), cell.guarantee)
    return {"seed": seed, "wrong_answers": wrong, "of": len(queries)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None, help="default: run_seconds")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        seconds = json.loads((Path(__file__).resolve().parent.parent / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in args.seeds:
        t = time.perf_counter()
        r = control_reading(cell, seed, seconds)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
