"""A rank of a `kexaone-train-t8192` rehearsal run with the timed path
broken underneath (for test_kexaone.py; never part of a benchmark run;
broken_mellum2_rank.py's twin for the kexaone_train runner).

    broken_kexaone_rank.py FAULT <rank_main's arguments>

`window_ignored`: the layers under the sliding window attend over the
whole causal triangle (the entry drops the window it is handed).
`window_one_key_wide`: the entry's window is one key too wide.
`full_rotated`: the full layers (and the MTP module) turn by the
windowed layers' RoPE where they take none.
`windowed_not_rotated`: the windowed layers take no rotation either.
`norm_over_the_projection`: q and k are normed over the whole
projection (the same gains, a head's repeated) in place of per head.
`mtp_windowed`: the MTP module's attention is under the window.
"""

import dataclasses
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

    attention, mixer = att.attention, tfm._attention

    def with_config(**changed):
        tfm._attention = lambda lp, x, cfg, *a, **kw: mixer(
            lp, x, dataclasses.replace(cfg, **changed), *a, **kw)

    if fault == "window_ignored":
        att.attention = lambda q, k, v, window=None, **kw: attention(
            q, k, v, **kw)
    elif fault == "window_one_key_wide":
        att.attention = lambda q, k, v, window=None, **kw: attention(
            q, k, v, window=window and window + 1, **kw)
    elif fault == "full_rotated":
        with_config(rope_full=None)
    elif fault == "windowed_not_rotated":
        with_config(rope_window=tfm.NO_ROPE)
    elif fault == "norm_over_the_projection":
        def whole(lp, x, cfg, *a, **kw):
            wide = {n: {"g": jnp.tile(lp[n]["g"], lp[w].shape[1]
                                      // lp[n]["g"].shape[0])}
                    for n, w in (("q_norm", "wq"), ("k_norm", "wk"))}
            return mixer(dict(lp, **wide), x,
                         dataclasses.replace(cfg, qk_norm=True), *a, **kw)

        tfm._attention = whole
    elif fault == "mtp_windowed":
        kind = tfm._mtp_kind
        tfm._mtp_kind = lambda cfg: kind(cfg)._replace(windowed=True)
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
