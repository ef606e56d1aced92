"""The norm of the first state-space layer's state after the last
token of the first batch, on the seed's weights: the program's counter
`ssm_state_norm_micro` (millionths), filled by its probe `ssm_probe` in
set-up (runners/nemotron_train.py). A scan that forgets everything
reads ~0, one that forgets nothing grows with the sequence."""


def read(run: dict):
    micro = run["counters"].get("ssm_state_norm_micro")
    return None if not micro else micro / 1e6
