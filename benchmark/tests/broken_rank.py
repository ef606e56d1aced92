"""A rank of a rehearsal run with the timed path broken underneath
(for test_broken_path.py; never part of a benchmark run).

    broken_rank.py FAULT <rank_main's arguments>

`unchanged_state`: the train step returns its state as it got it.
`skipped_rank`: the collective leaves one rank's contribution out.
`altered_answer`: one element of the collective's result is altered
where it is produced.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    from benchmark import rank_main
    from benchmark.collectives import allreduce
    from benchmark.runners import train_step

    if fault == "unchanged_state":
        build = train_step.build_step

        def broken_build(sizes, lr):
            import jax

            step = build(sizes, lr)

            def same_state(params, tokens, labels):
                new, loss = step.__wrapped__(params, tokens, labels)
                return params, loss

            return jax.jit(same_state, donate_argnums=(0,))

        train_step.build_step = broken_build
    elif fault in ("skipped_rank", "altered_answer"):
        real = allreduce.call

        def broken(comm, x, **kw):
            if fault == "skipped_rank" and comm.rank == comm.size - 1:
                x = x * 0
            y = real(comm, x, **kw)
            if fault == "altered_answer":
                y = y.at[0].add(1e-3)
            return y

        allreduce.call = broken
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
