"""A rank of a `glm5-train-t4096` rehearsal run with the timed path
broken underneath (for test_glm5.py; never part of a benchmark run;
broken_olmoe_rank.py's twin for the glm5_train runner).

    broken_glm5_rank.py FAULT <rank_main's arguments>

`unchanged_state`: the train step returns its state as it got it.
`selection_ignored`: every query attends to all its causal keys,
whatever the indexer scored.
`absent_experts_computed`: an assignment to an expert another chip
holds is given to one of this chip's (its number modulo the share)
instead of being left out.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    from benchmark import rank_main
    from benchmark.runners import glm5_train

    if fault == "unchanged_state":
        build = glm5_train.build_step

        def broken_build(sizes, lr):
            import jax

            step = build(sizes, lr)

            def same_state(params, tokens, labels):
                new, loss = step.__wrapped__(params, tokens, labels)
                return params, loss

            return jax.jit(same_state, donate_argnums=(0,))

        glm5_train.build_step = broken_build
    elif fault == "selection_ignored":
        from ompi_tpu.ops import attention as att

        att.dsa_select = lambda scores, topk: att._causal(
            scores.shape[-1], scores.shape[-1])
    elif fault == "absent_experts_computed":
        import jax.numpy as jnp

        from ompi_tpu.ops import moe

        def everything_is_mine(route, first, count):
            experts = route.experts % count
            return route._replace(
                experts=experts,
                counts=(experts.reshape(-1, 1) == jnp.arange(count)).sum(
                    0, dtype=jnp.int32))

        moe.held_share = everything_is_mine
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
