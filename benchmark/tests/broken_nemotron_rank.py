"""A rank of a `nemotron-train-t8192` rehearsal run with the timed path
broken underneath (for test_nemotron.py; never part of a benchmark run;
broken_glm5_rank.py's twin for the nemotron_train runner).

    broken_nemotron_rank.py FAULT <rank_main's arguments>

`carried_state_dropped`: every chunk of the scan starts from a zero
state (the product that carries the states between chunks is left out).
`wrong_key_heads`: each query head attends with the NEXT group's key
and value head.
`shared_expert_left_out`: the expert layers add their routed experts
alone.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    import jax.numpy as jnp

    from benchmark import rank_main
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.ops import attention as att
    from ompi_tpu.ops import ssm

    if fault == "carried_state_dropped":
        whole = ssm.chunked_scan

        def chunks_alone(x, dt, a, bm, cm, chunk):
            b, t = x.shape[:2]

            def cut(v):
                return v.reshape(b * (t // chunk), chunk, *v.shape[2:])

            y, last = whole(cut(x), cut(dt), a, cut(bm), cut(cm), chunk)
            return (y.reshape(x.shape),
                    last.reshape(b, t // chunk, *last.shape[1:])[:, -1])

        ssm.chunked_scan = chunks_alone
    elif fault == "wrong_key_heads":
        attention = att.attention

        def next_groups(q, k, v, **kw):
            per = q.shape[2] // 2  # the rehearsal has two key heads
            return attention(q, jnp.roll(k, per, axis=2),
                             jnp.roll(v, per, axis=2), **kw)

        att.attention = next_groups
    elif fault == "shared_expert_left_out":
        tfm._ffn = lambda x, w1, w3, w2, cfg: jnp.zeros_like(x)
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
