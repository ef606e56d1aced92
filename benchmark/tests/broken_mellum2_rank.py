"""A rank of a `mellum2-train-t16384` rehearsal run with the timed path
broken underneath (for test_mellum2.py; never part of a benchmark run;
broken_nemotron_rank.py's twin for the mellum2_train runner).

    broken_mellum2_rank.py FAULT <rank_main's arguments>

`window_ignored`: the layers under the sliding window attend over the
whole causal triangle (the entry drops the window it is handed).
`yarn_left_out`: the full layers turn by the windowed layers' plain
frequencies (no blended table, no attention factor).
`factor_left_out`: YaRN's frequencies without its attention factor on
cos and sin.
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    from benchmark import rank_main
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.ops import attention as att

    rope = tfm.rope
    if fault == "window_ignored":
        attention = att.attention

        def whole_triangle(q, k, v, window=None, **kw):
            return attention(q, k, v, **kw)

        att.attention = whole_triangle
    elif fault == "yarn_left_out":
        tfm.rope = lambda x, positions, theta: rope(
            x, positions, tfm.Rope(theta=theta.theta)
            if isinstance(theta, tfm.Rope) else theta)
    elif fault == "factor_left_out":
        tfm.rope = lambda x, positions, theta: rope(
            x, positions, dataclasses.replace(theta, attention_factor=1.0)
            if isinstance(theta, tfm.Rope) else theta)
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
