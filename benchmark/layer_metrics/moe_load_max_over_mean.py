"""Routing imbalance: the fullest expert's token-expert assignments
over the mean expert's, in the worst MoE layer of the worst check
batch — from the program's own probe `route_counts`, run in set-up on
the seed's state (runners/olmoe_train.py). 1.0 is perfectly balanced;
the grouped matmul's slowest group and, over several chips, the
busiest chip grow with it."""


def read(run: dict):
    return run["counters"].get("moe_load_max_over_mean")
