"""A rank of an `olmoe-train-t4096` rehearsal run with the timed path
broken underneath (for test_olmoe.py; never part of a benchmark run;
broken_rank.py's twin for the olmoe_train runner).

    broken_olmoe_rank.py FAULT <rank_main's arguments>

`unchanged_state`: the train step returns its state as it got it.
`dropped_assignments`: the program's probe loses one assignment per
layer, as a capacity would.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    from benchmark import rank_main
    from benchmark.runners import olmoe_train

    if fault == "unchanged_state":
        build = olmoe_train.build_step

        def broken_build(sizes, lr):
            import jax

            step = build(sizes, lr)

            def same_state(params, tokens, labels):
                new, loss = step.__wrapped__(params, tokens, labels)
                return params, loss

            return jax.jit(same_state, donate_argnums=(0,))

        olmoe_train.build_step = broken_build
    elif fault == "dropped_assignments":
        from ompi_tpu.models import transformer as tfm

        real = tfm._route_probe

        def lossy(*a, **kw):
            counts, experts = real(*a, **kw)
            return counts.at[:, 0].add(-1), experts

        tfm._route_probe = lossy
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
