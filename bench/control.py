#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the
control's numbers on the same queries, over several seeds in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed it does what one run of ``run_cell.py`` does up to the close
of the window (the same graph, engine, warm-up, traffic and window), then
compares the program's answers and the configuration's control (see its
deployment module's ``control``) with the reference, and prints one JSON
line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run_cell as rc  # noqa: E402  (sets the paths and the compile cache)

from bench.harness import loops, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.Cell(spec.benchmark(), args.workload)
    dep, cfg = cell.deployment, rc.configured(cell, args.tiny)

    import jax

    from repro.launch.env import enable_compile_cache

    if jax.devices()[0].platform != "tpu" and not args.tiny:
        rc.say("no TPU")
        return 2
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        rng_g, rng_p, rng_q, rng_a, rng_w = rc.rngs(seed)
        data = dep.build(cfg, rng_g)
        pool = dep.pool(data, cfg, rng_p)
        eng = dep.make_engine(data, cfg)
        rc.warm_up(eng, loops.cycle(pool, rng_w), cfg["capacity"])
        win = rc.run_window(eng, pool, cell.traffic, rng_q, rng_a,
                            args.seconds, False)
        queries = list(win.queries.values())
        answers = [dep.served(q.result) if q.status == "DONE" else None
                   for q in queries]
        del eng
        gc.collect()
        pairs = [q.pair for q in queries]
        ref = loops.per_pair(lambda ps: dep.reference(data, cfg, ps), pairs)
        program, wrong = dep.compare(answers, ref, cfg["limits"])
        ref_of = {tuple(int(v) for v in p): r for p, r in zip(pairs, ref)}
        ctrl = loops.per_pair(lambda ps: dep.control(
            data, cfg, ps, [ref_of[tuple(int(v) for v in p)] for p in ps]),
            pairs)
        control, cwrong = dep.compare(ctrl, ref, cfg["limits"])
        print(json.dumps(dict(
            workload=args.workload, seed=seed, queries=len(queries),
            unanswered=sum(a is None for a in answers),
            program={k: v for k, (v, _) in program.items()},
            program_failed=wrong,
            control={k: v for k, (v, _) in control.items()},
            control_failed=cwrong)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
