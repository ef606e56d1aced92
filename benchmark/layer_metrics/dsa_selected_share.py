"""The share of the causal (query, key) pairs that sparse attention
keeps: the program's counters `dsa_selected_pairs` /
`dsa_causal_pairs`, filled by its probe `dsa_selection` in set-up on
the seed's state (runners/glm5_train.py). 1.0 where nothing is
selected away; at 4,096 positions and top-2048 about 0.75."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.share(run, "dsa_selected_pairs", "dsa_causal_pairs")
