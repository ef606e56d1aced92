"""The norm of the first delta-rule layer's state after the last token
of the first batch, on the seed's weights: the program's counter
`kda_state_norm_micro` (millionths), filled by its probe `kda_probe` in
set-up (runners/solar2_train.py). A recurrence that forgets everything
reads ~0; one whose correction term is missing grows with the
sequence."""


def read(run: dict):
    micro = run["counters"].get("kda_state_norm_micro")
    return None if not micro else micro / 1e6
