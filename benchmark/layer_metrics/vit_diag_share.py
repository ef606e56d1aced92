"""The block-diagonal (query, key) pairs of the packed row of patches
over all its pairs, from the program's set-up probe
(ompi_tpu/models/vision.py `vision_stats`: counters `vision_diag_pairs`
/ `vision_row_pairs`): what a mask-blind attention kernel would waste
is the rest."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.share(run, "vision_diag_pairs", "vision_row_pairs")
