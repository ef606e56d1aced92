"""A rank of an `ouro-train-t4096` rehearsal run with the timed path
broken underneath (for test_ouro.py; never part of a benchmark run;
broken_glm5_rank.py's twin for the ouro_train runner).

    broken_ouro_rank.py FAULT <rank_main's arguments>

`unchanged_state`: the train step returns its state as it got it.
`stack_run_once`: the layer list runs ONCE and the state after that one
pass is reported for all four exits.
`no_norm_between_passes`: every pass starts from the state the last
one left, without the final norm (the exits still read normed states).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    from benchmark import rank_main
    from benchmark.runners import ouro_train

    if fault == "unchanged_state":
        build = ouro_train.build_step

        def broken_build(sizes, lr):
            import jax

            step = build(sizes, lr)

            def same_state(params, tokens, labels):
                new, loss = step.__wrapped__(params, tokens, labels)
                return params, loss

            return jax.jit(same_state, donate_argnums=(0,))

        ouro_train.build_step = broken_build
    elif fault == "stack_run_once":
        import dataclasses

        from ompi_tpu.models import transformer as tfm

        trunk = tfm._trunk

        def one_pass(params, tokens, cfg, ax, aux=None, index_aux=None,
                     exits=None):
            h, t_off = trunk(params, tokens,
                             dataclasses.replace(cfg, loops=1), ax, aux,
                             index_aux)
            if exits is not None:
                exits.extend([tfm._final_norm(params, h, cfg)]
                             * (cfg.loops - 1))
            return h, t_off

        tfm._trunk = one_pass
    elif fault == "no_norm_between_passes":
        from ompi_tpu.models import transformer as tfm

        trunk, norm = tfm._trunk, tfm._final_norm

        def unnormed(params, tokens, cfg, ax, aux=None, index_aux=None,
                     exits=None):
            tfm._final_norm = lambda params, h, cfg: h
            try:
                raw = []
                h, t_off = trunk(params, tokens, cfg, ax, aux, index_aux,
                                 raw)
            finally:
                tfm._final_norm = norm
            if exits is not None:
                exits.extend(norm(params, x, cfg) for x in raw)
            return h, t_off

        tfm._trunk = unnormed
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
