"""A rank of a `kimivl-train-t4096` rehearsal run with the timed path
broken underneath (for test_kimi_vl.py; never part of a benchmark run;
broken_glm5_rank.py's twin for the kimivl_train runner).

    broken_kimivl_rank.py FAULT <rank_main's arguments>

`segment_mask_dropped`: the tower's attention sees the whole packed
row, every image every other.
`tower_gradient_stopped`: the projector's rows reach the decoder as
constants: nothing of the tower or the projector learns.
`rows_one_late`: the merged rows land one position later in the
sequence than the batch says.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    fault = sys.argv.pop(1)
    from benchmark import rank_main
    from ompi_tpu.models import vision
    from ompi_tpu.ops import attention as att

    if fault == "segment_mask_dropped":
        attention = att.attention

        def every_image_sees_every_other(q, k, v, causal=True, scale=None,
                                         q_offset=0, k_offset=0,
                                         segments=None):
            if segments is not None:
                return att.mha(q, k, v, causal=False, scale=scale)
            return attention(q, k, v, causal, scale, q_offset, k_offset)

        att.attention = every_image_sees_every_other
    elif fault == "tower_gradient_stopped":
        import jax

        place = vision.place
        vision.place = lambda h, rows, where: place(
            h, jax.lax.stop_gradient(rows), where)
    elif fault == "rows_one_late":
        place = vision.place
        vision.place = lambda h, rows, where: place(h, rows, where + 1)
    else:
        raise SystemExit(f"no fault {fault!r}")
    return rank_main.main()


if __name__ == "__main__":
    sys.exit(main())
