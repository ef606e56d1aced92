"""A rank of a `solar2-train-t8192` rehearsal run with the timed path
broken underneath (for test_solar2.py; never part of a benchmark run;
broken_nemotron_rank.py's twin for the solar2_train runner).

    broken_solar2_rank.py FAULT <rank_main's arguments>

`decay_ignored`: the delta rule runs with g = 0 (nothing is ever
forgotten but what the correction removes).
`correction_left_out`: the delta rule's correction term is left out —
plain gated linear attention, S_t = Diag(exp(g)) S_{t-1} + beta k v^T.
`kda_gate_left_out`: the delta-rule layers' output is normed and never
gated.
`carried_state_dropped`: every chunk starts from a zero state.
`gqa_gate_left_out`: attention's output goes ungated into W_o.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    import jax.numpy as jnp

    from benchmark import rank_main
    from ompi_tpu.ops import kda

    whole = kda.chunked_delta
    if fault == "decay_ignored":
        kda.chunked_delta = lambda q, k, v, g, beta, chunk, per=None: whole(
            q, k, v, jnp.zeros_like(g), beta, chunk, per)
    elif fault == "correction_left_out":
        pairs = kda.decayed_pairs

        def no_system(q, k, cum, sub=kda.SUB):
            p, kk = pairs(q, k, cum, sub)
            return p, jnp.zeros_like(kk)  # (I + A) = I: W, U uncorrected

        def no_reading(w, u, kd, grown):  # V' = U: the state is not read
            return kda_scan(jnp.zeros_like(w), u, kd, grown)

        kda_scan = kda.scan_carry
        kda.decayed_pairs, kda.scan_carry = no_system, no_reading
    elif fault == "kda_gate_left_out":
        heads = kda._heads

        def ungated(small, q, k, v, f, gate, beta, **kw):
            return heads(small, q, k, v, f, jnp.full_like(gate, 30.0), beta,
                         **kw)

        kda._heads = ungated
    elif fault == "carried_state_dropped":
        carry = kda.scan_carry
        kda.scan_carry = lambda w, u, kd, grown: carry(
            w, u, kd, jnp.zeros_like(grown))
    elif fault == "gqa_gate_left_out":
        from ompi_tpu.models import transformer as tfm

        attention = tfm._attention

        def ungated(lp, x, cfg, *a, **kw):
            return attention(dict(lp, wa=jnp.zeros_like(lp["wa"])), x, cfg,
                             *a, **kw) * 2.0  # sigmoid(0) = 1/2

        tfm._attention = ungated
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
