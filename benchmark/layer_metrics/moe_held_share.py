"""The share of the token-expert assignments that fall to the experts
THIS chip holds: the program's counters `moe_held_assignments` /
`moe_assignments`, filled by its probe `route_counts` in set-up on the
seed's state (runners/glm5_train.py). 8 of 256 experts held: about
1/32 under uniform routing; the rest are other chips' work and are
computed by nobody here."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.share(run, "moe_held_assignments", "moe_assignments")
